"""A dense decoder LM of the Qwen2 family: sizes, seeded weights, and a plain
float32 reference forward, kept with the benchmark.

The reference follows the published Qwen2 equations (``Qwen2ForCausalLM``):
token embedding, then per layer RMSNorm -> q/k/v projections with bias ->
rotary embedding (half-split, ``rope_theta``) -> causal softmax attention ->
o projection -> residual, RMSNorm -> SwiGLU MLP (down(silu(gate x) * up x))
-> residual; a final RMSNorm and the LM head, tied to the embedding. It is
plain ``jax.numpy`` in float32, every matmul at ``Precision.HIGHEST``, with
no cache and no batching tricks, run layer by layer on blocks of sequences.

It imports nothing of the program. The weights are made here from the seed,
in the layout the program's ``repro.models.lm`` reads, and both sides read
the same bfloat16 values:

* the program's RMSNorm computes ``x * (1 + scale)``: the reference's norm
  weight is ``1 + scale``, in float32, exactly;
* the program multiplies the embedding by ``sqrt(d_model)``: its embedding
  leaf holds ``E / sqrt(d_model)`` and its LM head ``E^T``, so with
  ``d_model = 1024`` (``sqrt = 32``) both sides see the same tied ``E``.
"""

from __future__ import annotations

import math

import numpy as np


#: bytes of one element in each ``torch_dtype`` the model may be served in
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(cfg: dict) -> dict:
    """The model's sizes from a Hugging Face ``config.json``."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "dtype_bytes": DTYPE_BYTES[cfg["torch_dtype"]],
        "d_model": d,
        "n_heads": H,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", d // H),
        "d_ff": cfg["intermediate_size"],
        "n_layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "std": float(cfg["initializer_range"]),
    }


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration, as published."""
    from repro.models.config import ModelConfig

    s = sizes(cfg)
    if cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"]:
        raise ValueError("this reference covers SwiGLU models with a tied head")
    if cfg.get("use_sliding_window"):
        raise ValueError("sliding-window attention is not covered")
    if s["eps"] != 1e-6:
        raise ValueError("the program's RMSNorm fixes eps at 1e-6")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=s["n_layers"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"], d_ff=s["d_ff"],
        vocab=s["vocab"], head_dim=s["head_dim"], qkv_bias=True,
        rope_theta=s["rope_theta"], dtype=cfg["torch_dtype"])


def init_params(seed_key: int, s: dict):
    """Seeded bfloat16 weights in the program's layout, made on the device in
    one jitted call."""
    import jax
    import jax.numpy as jnp

    L, d, H, Hkv, hd, ff, V = (s["n_layers"], s["d_model"], s["n_heads"],
                               s["n_kv_heads"], s["head_dim"], s["d_ff"], s["vocab"])
    std = s["std"]
    bf = jnp.bfloat16

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale=std):
            return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(bf)

        def uniform(shape):  # norm weight - 1, in [-0.5, 0.5)
            return (jax.random.uniform(next(ks), shape, jnp.float32) - 0.5).astype(bf)

        emb = normal((V, d))  # the tied E
        layers = {
            "ln1": {"scale": uniform((L, d))},
            "attn": {"wq": normal((L, d, H * hd)), "wk": normal((L, d, Hkv * hd)),
                     "wv": normal((L, d, Hkv * hd)), "wo": normal((L, H * hd, d)),
                     "bq": normal((L, H * hd), 0.1), "bk": normal((L, Hkv * hd), 0.1),
                     "bv": normal((L, Hkv * hd), 0.1)},
            "ln2": {"scale": uniform((L, d))},
            "mlp": {"wi": normal((L, d, ff)), "wg": normal((L, d, ff)),
                    "wo": normal((L, ff, d))},
        }
        return {
            "embedding": {"embed": (emb.astype(jnp.float32) / math.sqrt(d)).astype(bf),
                          "head": emb.T},
            "layers": layers,
            "ln_f": {"scale": uniform((d,))},
        }

    return jax.jit(make)(jax.random.key(seed_key))


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------


def _fp8_rows(w):
    """Weights rounded through float8_e4m3fn with one scale per output column
    (the lower-precision control)."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale)


def _make_fns(s: dict, control: bool):
    """Jitted layer and head functions; float32 at highest precision, or for
    the control fp8 weights with bfloat16 activations."""
    import jax
    import jax.numpy as jnp

    H, Hkv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    eps, theta = s["eps"], s["rope_theta"]
    act = jnp.bfloat16 if control else jnp.float32
    prec = jax.lax.Precision.DEFAULT if control else jax.lax.Precision.HIGHEST

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec)

    def w(x):  # a weight matrix as the mode computes with it
        x = _fp8_rows(x) if control else x.astype(jnp.float32)
        return x.astype(act)

    def vec(x):
        return x.astype(jnp.float32).astype(act)

    def rms(x, scale):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(act)

    def rope(x, pos):  # x (b, S, h, hd)
        half = hd // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(act)

    def layer(x, p):
        b, S, _ = x.shape
        pos = jnp.arange(S)
        h = rms(x, p["ln1"]["scale"])
        a = p["attn"]
        q = (mm(h, w(a["wq"])) + vec(a["bq"])).reshape(b, S, H, hd)
        k = (mm(h, w(a["wk"])) + vec(a["bk"])).reshape(b, S, Hkv, hd)
        v = (mm(h, w(a["wv"])) + vec(a["bv"])).reshape(b, S, Hkv, hd)
        q, k = rope(q, pos), rope(k, pos)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        causal = pos[:, None] >= pos[None, :]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(act)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=prec).reshape(b, S, H * hd)
        x = (x + mm(o, w(a["wo"]))).astype(act)
        h = rms(x, p["ln2"]["scale"])
        m = p["mlp"]
        up = mm(h, w(m["wi"]))
        gate = jax.nn.silu(mm(h, w(m["wg"])).astype(jnp.float32)).astype(act)
        return (x + mm(gate * up, w(m["wo"]))).astype(act)

    def head(x, ln_f, head_w, idx):  # x (b, S, d); idx (P,) positions
        return mm(rms(x[:, idx], ln_f), w(head_w)).astype(jnp.float32)

    def embed(emb, tokens):
        return (emb.astype(jnp.float32) * math.sqrt(s["d_model"]))[tokens].astype(act)

    return jax.jit(layer), jax.jit(head), jax.jit(embed)


def reference_logits(params, s: dict, tokens: np.ndarray, positions: np.ndarray, *,
                     control: bool = False, block: int = 4) -> np.ndarray:
    """Logits (n, P, vocab) float32 at ``positions`` of each of the n token
    rows, computed layer by layer on blocks of ``block`` rows."""
    import jax

    layer, head, embed = _make_fns(s, control)
    tokens = np.asarray(tokens, np.int32)
    idx = np.asarray(positions, np.int32)
    out = []
    for lo in range(0, tokens.shape[0], block):
        x = embed(params["embedding"]["embed"], tokens[lo:lo + block])
        for i in range(s["n_layers"]):
            p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x = layer(x, p)
        out.append(np.asarray(head(x, params["ln_f"]["scale"],
                                   params["embedding"]["head"], idx)))
        del x
    return np.concatenate(out)


def widest_gap(ref: np.ndarray, chosen: np.ndarray) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best at that position. ref: (n, P, V); chosen: (n, P)."""
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, chosen[..., None].astype(np.int64), axis=-1)[..., 0]
    return float((best - got).max())
