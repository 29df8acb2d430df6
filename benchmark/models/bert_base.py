"""BERT-base for the benchmark: the net through the repo's public API, the
plain float32 reference, and the operations and bytes of one train step
worked out from the shapes.  Every size comes from the configuration file.
"""
import numpy as np


# -- the system under test --------------------------------------------------

def build(config, ctx, seed):
    """model_zoo.bert.get_bert on `ctx`, cast, hybridized, deferred shapes
    resolved (the first forward of a net with deferred shapes is
    imperative: 2 rows; without it the first ``step.step`` is an eager
    step and the next one compiles inside the window - PR 22, fault b)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import bert
    mx.random.seed(seed)
    net = bert.BERTModel(
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        vocab_size=config["vocab_size"],
        token_type_vocab_size=config["type_vocab_size"],
        max_length=config["max_position_embeddings"], dropout=0.0,
        use_classifier=False)
    net.initialize(mx.init.Normal(0.02), ctx=ctx)
    net.cast(config["dtype"])
    net.hybridize()
    two = nd.zeros((2, config["max_position_embeddings"]), ctx=ctx,
                   dtype="int32")
    net(two, two)
    return net


def loss_fn():
    from mxnet_tpu import gluon
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(outs, label):
        return sce(outs[-1].astype("float32"), label)    # (B, T, vocab)

    return mlm_loss


def batches(config, traffic, seed):
    """The pool of host batches: ((tokens, segments), labels) each."""
    rng = np.random.RandomState(seed)
    b, t, v = traffic["batch"], traffic["seq"], config["vocab_size"]
    pool = []
    for _ in range(traffic["pool"]):
        tok = rng.randint(0, v, (b, t)).astype(np.int32)
        seg = np.zeros((b, t), np.int32)
        lab = rng.randint(0, v, (b, t)).astype(np.float32)
        pool.append(((tok, seg), lab))
    return pool


def units_per_row(traffic):
    """Tokens in one row of a batch (the throughput's unit)."""
    return traffic["seq"]


def check_inputs(config, traffic, seed):
    rng = np.random.RandomState(seed + 1)
    t = traffic["seq"]
    tok = rng.randint(0, config["vocab_size"], (4, t)).astype(np.int32)
    return (tok, np.zeros((4, t), np.int32))


def logits(net, inputs, ctx):
    """The net's MLM logits for `inputs`, a float32 jax array."""
    from mxnet_tpu import nd
    tok, seg = (nd.array(a, ctx=ctx, dtype="int32") for a in inputs)
    return net(tok, seg)[-1]._jax.astype("float32")


# -- the plain reference ----------------------------------------------------

def reference(params, inputs, config):
    """BERT's forward pass as Devlin et al. 2018 describe it (post-LN
    encoder, erf GELU), with the model zoo's untied MLM head, in float32
    ``jax.numpy`` at the highest matmul precision: no Gluon, no kernels.
    `params` maps the net's parameter names to arrays."""
    import jax
    import jax.numpy as jnp
    heads = config["num_attention_heads"]

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def dense(x, name):
        return x @ p(name + ".weight").T + p(name + ".bias")

    def layer_norm(x, name):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * p(name + ".gamma") \
            + p(name + ".beta")

    def gelu(x):
        return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))

    with jax.default_matmul_precision("highest"):
        tok, seg = (jnp.asarray(a) for a in inputs)
        n, t = tok.shape
        x = p("word_embed.weight")[tok] + p("token_type_embed.weight")[seg] \
            + p("position_embed.weight")[:t][None]
        x = layer_norm(x, "embed_layer_norm")
        for i in range(config["num_hidden_layers"]):
            cell = "encoder.transformer_cells.%d." % i
            qkv = dense(x, cell + "attention.query_key_value")
            q, k, v = (a.reshape(n, t, heads, -1).transpose(0, 2, 1, 3)
                       for a in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(q.shape[-1])
            att = jax.nn.softmax(scores, axis=-1) @ v
            att = att.transpose(0, 2, 1, 3).reshape(n, t, -1)
            x = layer_norm(x + dense(att, cell + "attention.proj"),
                           cell + "layer_norm_att")
            ffn = dense(gelu(dense(x, cell + "ffn.ffn_1")),
                        cell + "ffn.ffn_2")
            x = layer_norm(x + ffn, cell + "layer_norm_ffn")
        h = layer_norm(gelu(dense(x, "decoder_transform")), "decoder_norm")
        return dense(h, "decoder_out")


# -- operations and bytes of one train step, from the shapes ----------------

def ops_and_bytes(config, traffic):
    """Required floating-point operations and least HBM bytes of ONE
    train step of the whole (global) batch.

    Operations: matrix products only, 2 per multiply-add, forward once
    and backward twice (gradient by the input and by the weight).
    Embedding look-ups, norms, GELU and softmax are not matrix products
    and count 0; the pooler feeds no loss and counts 0; nothing is
    counted twice for being recomputed.  Bytes: what any implementation
    must move - the batch in, every parameter with its float32 master
    copy and momentum read once and written once."""
    b, t = traffic["batch"], traffic["seq"]
    d, f = config["hidden_size"], config["intermediate_size"]
    v, layers = config["vocab_size"], config["num_hidden_layers"]
    tokens = b * t
    per_layer = {
        "qkv": 2 * tokens * d * 3 * d,
        "attention_scores": 2 * b * t * t * d,     # all heads: H * (T*T*D)
        "attention_values": 2 * b * t * t * d,
        "proj": 2 * tokens * d * d,
        "ffn": 2 * 2 * tokens * d * f,
    }
    head = {"mlm_transform": 2 * tokens * d * d,
            "mlm_output": 2 * tokens * d * v}
    forward = layers * sum(per_layer.values()) + sum(head.values())
    matmul_params = layers * (3 * d * d + d * d + 2 * d * f) + d * d + d * v
    other_params = (v + config["type_vocab_size"]
                    + config["max_position_embeddings"]) * d \
        + layers * (3 * d + d + f + d + 4 * d) + 2 * d + d + 2 * d + v \
        + d * d + d                                   # pooler: held, unused
    n_params = matmul_params + other_params
    state_bytes = n_params * (2 + 4 + 4)              # bf16 + master + momentum
    batch_bytes = tokens * (4 + 4 + 4)                # tokens, segments, labels
    return {"flops": 3 * forward, "forward_flops": forward,
            "bytes": 2 * state_bytes + batch_bytes,
            "n_params": n_params,
            "detail": {"per_layer_forward": per_layer, "head_forward": head}}
