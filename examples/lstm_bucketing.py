"""Classic bucketed LSTM language model (reference:
example/rnn/bucketing/lstm_bucketing.py).

The full pre-Gluon stack end to end: mx.rnn.BucketSentenceIter bins
variable-length sentences into buckets, a sym_gen builds one unrolled
graph per bucket with mx.rnn symbolic cells (weights shared across
buckets through the names), and BucketingModule.fit switches compiled
executables per batch.  Offline it runs on synthetic sentences; point
MX_DATA_DIR at a PTB-style corpus (one sentence per line of ints) to
arm it.

    python examples/lstm_bucketing.py [--num-epochs 2] [--num-layers 2]
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def load_sentences(vocab):
    data_dir = os.environ.get("MX_DATA_DIR")
    path = data_dir and os.path.join(data_dir, "ptb", "ptb.train.txt")
    if path and os.path.exists(path):
        words = {}
        sentences = []
        with open(path) as f:
            for line in f:
                ids = []
                for w in line.split() + ["</s>"]:
                    ids.append(words.setdefault(w, len(words) % vocab))
                sentences.append(ids)
        return sentences
    rng = np.random.RandomState(0)
    # synthetic: Markov-ish sentences so perplexity actually falls
    sentences = []
    for _ in range(600):
        n = rng.randint(5, 40)
        s = [int(rng.randint(1, vocab))]
        for _ in range(n - 1):
            s.append(int((s[-1] * 7 + rng.randint(0, 3)) % vocab))
        sentences.append(s)
    return sentences


def main():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--num-hidden", type=int, default=64)
    ap.add_argument("--num-embed", type=int, default=32)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=200)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[10, 20, 30, 40])
    args = ap.parse_args()

    sentences = load_sentences(args.vocab)
    data_iter = mx.rnn.BucketSentenceIter(
        sentences, args.batch_size, buckets=args.buckets,
        invalid_label=0)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data=data, input_dim=args.vocab,
                                 output_dim=args.num_embed, name="embed")
        stack = mx.rnn.SequentialRNNCell()
        for i in range(args.num_layers):
            stack.add(mx.rnn.LSTMCell(args.num_hidden,
                                      prefix="lstm_l%d_" % i))
        outputs, _ = stack.unroll(seq_len, inputs=embed,
                                  merge_outputs=True)
        pred = mx.sym.reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(data=pred, num_hidden=args.vocab,
                                     name="pred")
        lab = mx.sym.reshape(label, shape=(-1,))
        net = mx.sym.SoftmaxOutput(data=pred, label=lab, name="softmax")
        return net, ("data",), ("softmax_label",)

    model = mx.mod.BucketingModule(
        sym_gen, default_bucket_key=data_iter.default_bucket_key,
        context=mx.tpu(0))
    model.fit(
        data_iter,
        eval_metric=mx.metric.Perplexity(ignore_label=0),
        optimizer="sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.0,
                          "wd": 1e-5, "clip_gradient": 0.25},
        initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
        num_epoch=args.num_epochs,
        batch_end_callback=mx.callback.Speedometer(
            args.batch_size, frequent=20),
    )
    data_iter.reset()
    final = model.score(data_iter,
                        mx.metric.Perplexity(ignore_label=0))
    print("final train perplexity: %.2f" % dict(final)["perplexity"])


if __name__ == "__main__":
    main()
