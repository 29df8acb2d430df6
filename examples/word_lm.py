"""LSTM word-level language model on PTB (BASELINE config 3; reference:
example/rnn/word_lm/train.py — the cuDNN-RNN → XLA-scan headline config).

Trains a tied-embedding LSTM LM with truncated BPTT (hidden state carried
across batches and DETACHED — the reference's `hidden = detach(hidden)`
pattern) and reports per-epoch perplexity + words/sec (Speedometer-style
logging that tools/parse_log.py scrapes).

Real data when ``MX_DATA_DIR/ptb/ptb.train.txt`` exists; otherwise a
synthetic Zipf-distributed corpus keeps the script runnable offline:

    python examples/word_lm.py [--epochs 1] [--bptt 35] [--batch-size 20]
"""
import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, nd  # noqa: E402
from mxnet_tpu.gluon import nn, rnn  # noqa: E402


def load_corpus(vocab_size):
    """token-id stream: PTB if dropped at MX_DATA_DIR, else synthetic."""
    data_dir = os.environ.get("MX_DATA_DIR")
    path = data_dir and os.path.join(data_dir, "ptb", "ptb.train.txt")
    if path and os.path.exists(path):
        with open(path) as f:
            words = f.read().replace("\n", " <eos> ").split()
        vocab = {}
        ids = []
        for w in words:
            if w not in vocab and len(vocab) < vocab_size - 1:
                vocab[w] = len(vocab)
            ids.append(vocab.get(w, vocab_size - 1))
        return np.asarray(ids, np.int32), max(len(vocab) + 1, 2)
    # offline: Zipf tokens with Markov structure so the LM has signal
    rng = np.random.RandomState(0)
    n = 40_000
    base = rng.zipf(1.5, n).clip(1, vocab_size - 1)
    ids = np.where(np.arange(n) % 2 == 1,
                   (base * 7 + 3) % vocab_size, base)  # learnable bigram
    return ids.astype(np.int32), vocab_size


def batchify(ids, batch_size):
    nb = len(ids) // batch_size
    return ids[:nb * batch_size].reshape(batch_size, nb).T  # (T, N)


class RNNModel(gluon.HybridBlock):
    """Embedding → LSTM → tied-weight decoder (reference word_lm model)."""

    def __init__(self, vocab_size, embed_size, hidden_size, layers,
                 dropout):
        super().__init__()
        self.embedding = nn.Embedding(vocab_size, embed_size)
        self.lstm = rnn.LSTM(hidden_size, num_layers=layers,
                             dropout=dropout, input_size=embed_size)
        self.drop = nn.Dropout(dropout)
        self.proj = nn.Dense(embed_size, in_units=hidden_size,
                             flatten=False)
        self.vocab_size = vocab_size

    def forward(self, x, state):
        emb = self.drop(self.embedding(x))          # (T, N, E)
        out, state = self.lstm(emb, state)
        out = self.proj(self.drop(out))             # (T, N, E)
        # tied decoder: logits = out @ embedding.weightᵀ
        w = self.embedding.weight.data(out.context)
        logits = nd.invoke("dot", out.reshape((-1, w.shape[1])), w,
                           transpose_b=True)
        return logits.reshape((x.shape[0], x.shape[1], -1)), state


def detach(state):
    return [s.detach() for s in state] if isinstance(state, (list, tuple)) \
        else state.detach()


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=20)
    p.add_argument("--bptt", type=int, default=35)
    p.add_argument("--vocab", type=int, default=2000)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=1)
    p.add_argument("--dropout", type=float, default=0.2)
    p.add_argument("--lr", type=float, default=1.0)
    p.add_argument("--max-batches", type=int, default=0,
                   help="cap batches/epoch (CI smoke)")
    args = p.parse_args()

    mx.random.seed(0)
    ids, vocab = load_corpus(args.vocab)
    data = batchify(ids, args.batch_size)           # (T_total, N)
    model = RNNModel(vocab, args.embed, args.hidden, args.layers,
                     args.dropout)
    model.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(model.collect_params(), "sgd",
                            {"learning_rate": args.lr, "clip_gradient": 0.25})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    n_batches = (data.shape[0] - 1) // args.bptt
    if args.max_batches:
        n_batches = min(n_batches, args.max_batches)
    for epoch in range(args.epochs):
        state = model.lstm.begin_state(args.batch_size)
        total_nll, total_words = 0.0, 0
        tic = time.time()
        for i in range(n_batches):
            s = i * args.bptt
            x = nd.array(data[s:s + args.bptt])
            y = nd.array(data[s + 1:s + 1 + args.bptt].astype(np.float32))
            state = detach(state)                  # truncated BPTT
            with autograd.record():
                logits, state = model(x, state)
                loss = loss_fn(logits, y)
            loss.backward()
            trainer.step(args.batch_size * args.bptt)
            total_nll += float(loss.mean().asnumpy()) * x.size
            total_words += x.size
        ppl = math.exp(total_nll / total_words)
        wps = total_words / (time.time() - tic)
        print("Epoch[%d] Train-perplexity=%.2f" % (epoch, ppl))
        print("Epoch[%d] Speed: %.1f samples/sec" % (epoch, wps))
    print("final train perplexity %.2f (vocab=%d)" % (ppl, vocab))


if __name__ == "__main__":
    main()
