"""A decoder LM of the DeepSeek-V3 family (multi-head latent attention, routed
and shared experts, leading dense layers): sizes, seeded weights, and a
plain float32 reference forward, kept with the benchmark.

The reference follows the published equations (DeepSeek-V3, arXiv:2412.19437
§2.1; its attention is DeepSeek-V2's MLA, arXiv:2405.04434 §2.1.2-2.1.3),
as ``DeepseekV3ForCausalLM`` computes them. Per layer: RMSNorm -> MLA with
no query compression (q = x W_q split into a "nope" and a rotary part; the
latent [c; k_r] = x W_kv_a, c RMS-normed, RoPE on k_r, one rotary key for
every head; [k_nope; v] = c W_kv_b; causal softmax over q.k / sqrt(nope +
rope); W_o) -> residual, RMSNorm -> MLP -> residual. The first
``first_k_dense_replace`` layers have a SwiGLU MLP of ``intermediate_size``;
the rest an MoE layer: sigmoid scores of every routed expert, the top
``num_experts_per_tok`` by score plus ``e_score_correction_bias`` (groups
of one: ``n_group`` = ``topk_group`` = 1), their scores (without the bias)
renormalised and times ``routed_scaling_factor``, the chosen experts'
SwiGLU outputs summed with those weights, plus the shared experts (one
SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``). Then a final
RMSNorm and the untied LM head. It is plain ``jax.numpy`` in float32, every
matmul at ``Precision.HIGHEST``, keys and values decompressed, with no cache
and no batching tricks, run layer by layer on blocks of sequences; each
expert is applied densely to every token and weighted by its (zero unless
chosen) gate.

It imports nothing of the program. The weights are made here from the seed,
in the layout the program's ``repro.models.lm`` reads, and both sides read
the same bfloat16 values. Departures from the published model, each applied
alike in the program and here:

* expert parallelism: the configuration's ``n_routed_experts`` counts the
  experts this chip holds (``first_held_expert`` on); the router scores all
  ``n_routed_experts_published`` of them, and the routed sum covers the held
  experts only, as one chip of the stated deployment computes it;
* the program's RMSNorm computes ``x * (1 + scale)``: the reference's norm
  weight is ``1 + scale``, in float32, exactly;
* the program multiplies the embedding by ``sqrt(d_model)``, which the
  published model does not: its embedding leaf holds ``E / sqrt(d_model)``
  and the reference embeds ``leaf * sqrt(d_model)``, which is ``E`` up to
  the leaf's bfloat16 rounding, the same values on both sides;
* RoPE rotates the halves of each rotary vector (x1, x2) = (x[:r/2],
  x[r/2:]); the published checkpoint stores rotary weights interleaved, a
  fixed permutation of W_q's and W_kv_a's rotary columns, which random
  weights do not have;
* the correction bias is drawn from the seed, not zero, so that the check
  sees it take part in the choice and not in the weights; the router's
  weights are bfloat16 values held in float32, as the published router is
  computed.
"""

from __future__ import annotations

import math

import numpy as np

from benchlib.dense_lm import DTYPE_BYTES, _fp8_rows

__all__ = ["sizes", "program_config", "init_params", "held_margin", "reference_forward",
           "reference_logits", "widest_gap"]


def sizes(cfg: dict) -> dict:
    """The model's sizes from its ``config.json``, with the expert share."""
    return {
        "dtype_bytes": DTYPE_BYTES[cfg["torch_dtype"]],
        "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_layers": cfg["num_hidden_layers"],
        "n_dense_layers": cfg["first_k_dense_replace"],
        "d_ff_dense": cfg["intermediate_size"],
        "d_ff": cfg["moe_intermediate_size"],
        "n_experts": cfg["n_routed_experts_published"],
        "experts_held": cfg["n_routed_experts"],
        "expert_first": cfg["first_held_expert"],
        "top_k": cfg["num_experts_per_tok"],
        "n_shared": cfg["n_shared_experts"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope": cfg["qk_nope_head_dim"],
        "qk_rope": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "vocab": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "std": float(cfg["initializer_range"]),
        "route_margin": float(cfg["limits"]["route_margin"]),
    }


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration, as published."""
    from repro.models.config import ModelConfig

    s = sizes(cfg)
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("this reference covers SwiGLU models with an untied head")
    if cfg["q_lora_rank"] is not None or cfg.get("rope_scaling"):
        raise ValueError("query compression and RoPE scaling are not covered")
    if (cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["moe_layer_freq"] != 1 or not cfg["norm_topk_prob"]):
        raise ValueError("this reference covers renormalised sigmoid top-k routing in "
                         "groups of one")
    if not 0 <= s["expert_first"] <= s["n_experts"] - s["experts_held"]:
        raise ValueError("the held experts lie outside the routed ones")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=s["n_layers"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_heads"], d_ff=s["d_ff"], vocab=s["vocab"],
        kv_lora_rank=s["kv_lora_rank"], qk_nope_head_dim=s["qk_nope"],
        qk_rope_head_dim=s["qk_rope"], v_head_dim=s["v_head_dim"],
        rope_theta=s["rope_theta"], n_experts=s["n_experts"], top_k=s["top_k"],
        router_score="sigmoid", routed_scale=s["routed_scale"],
        n_shared_experts=s["n_shared"], first_dense_layers=s["n_dense_layers"],
        d_ff_dense=s["d_ff_dense"], expert_first=s["expert_first"],
        experts_held=s["experts_held"], norm_eps=s["eps"], dtype=cfg["torch_dtype"])


def init_params(seed_key: int, s: dict):
    """Seeded bfloat16 weights in the program's layout, made on the device in
    one jitted call, a layer (or a slice of the vocabulary) at a time."""
    import jax
    import jax.numpy as jnp

    d, H, V = s["d_model"], s["n_heads"], s["vocab"]
    c, nope, r, dv = s["kv_lora_rank"], s["qk_nope"], s["qk_rope"], s["v_head_dim"]
    E, E_all, f = s["experts_held"], s["n_experts"], s["d_ff"]
    std = s["std"]
    bf = jnp.bfloat16

    def draws(key):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, scale=std):
            return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(bf)

        def uniform(shape):  # norm weight - 1, in [-0.5, 0.5)
            return (jax.random.uniform(next(ks), shape, jnp.float32) - 0.5).astype(bf)

        return normal, uniform

    def block(key, ff, moe):
        normal, uniform = draws(key)
        p = {
            "ln1": {"scale": uniform((d,))},
            "attn": {"wq": normal((d, H * (nope + r))), "wkv_a": normal((d, c + r)),
                     "kv_norm": {"scale": uniform((c,))},
                     "wkv_b": normal((c, H * (nope + dv))), "wo": normal((H * dv, d))},
            "ln2": {"scale": uniform((d,))},
        }
        if not moe:
            p["mlp"] = {"wi": normal((d, ff)), "wg": normal((d, ff)), "wo": normal((ff, d))}
            return p
        fs = s["n_shared"] * f
        p["moe"] = {
            "router": normal((d, E_all)).astype(jnp.float32),
            "router_bias": normal((E_all,), 0.05).astype(jnp.float32),
            "wi": normal((E, d, f)), "wg": normal((E, d, f)), "wo": normal((E, f, d)),
            "shared": {"wi": normal((d, fs)), "wg": normal((d, fs)), "wo": normal((fs, d))},
        }
        return p

    def rows(key, shape, chunks=8):  # a (V, d) or (d, V) table, V in slices
        keys = jax.random.split(key, chunks)
        if shape[0] == V:
            part = jax.lax.map(lambda k: draws(k)[0]((V // chunks, d)), keys)
            return part.reshape(V, d)
        part = jax.lax.map(lambda k: draws(k)[0]((d, V // chunks)), keys)
        return jnp.moveaxis(part, 0, 1).reshape(d, V)

    def make(key):
        k_emb, k_head, k_dense, k_moe, k_ln = jax.random.split(key, 5)
        n_dense = s["n_dense_layers"]
        emb = rows(k_emb, (V, d))
        params = {
            "embedding": {"embed": (emb.astype(jnp.float32) / math.sqrt(d)).astype(bf),
                          "head": rows(k_head, (d, V))},
            "layers": jax.lax.map(lambda k: block(k, f, True),
                                  jax.random.split(k_moe, s["n_layers"] - n_dense)),
            "ln_f": {"scale": draws(k_ln)[1]((d,))},
        }
        if n_dense:
            params["dense_layers"] = jax.lax.map(lambda k: block(k, s["d_ff_dense"], False),
                                                 jax.random.split(k_dense, n_dense))
        return params

    if V % 8:
        raise ValueError("the vocabulary is drawn in eight slices")
    return jax.jit(make)(jax.random.key(seed_key))


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------


def _make_fns(s: dict, control: bool):
    """Jitted layer and head functions; float32 at highest precision, or for
    the control fp8 weights with bfloat16 activations."""
    import jax
    import jax.numpy as jnp

    H, c = s["n_heads"], s["kv_lora_rank"]
    nope, r, dv = s["qk_nope"], s["qk_rope"], s["v_head_dim"]
    E, K, first = s["experts_held"], s["top_k"], s["expert_first"]
    eps, theta = s["eps"], s["rope_theta"]
    act = jnp.bfloat16 if control else jnp.float32
    prec = jax.lax.Precision.DEFAULT if control else jax.lax.Precision.HIGHEST

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec)

    def w(x):  # a weight matrix as the mode computes with it
        x = _fp8_rows(x) if control else x.astype(jnp.float32)
        return x.astype(act)

    def rms(x, scale):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(act)

    def rope(x, pos):  # x (b, S, h, r)
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(act)

    def swiglu(h, m):
        up = mm(h, w(m["wi"]))
        gate = jax.nn.silu(mm(h, w(m["wg"])).astype(jnp.float32)).astype(act)
        return mm(gate * up, w(m["wo"]))

    def attend(x, a):
        b, S, _ = x.shape
        pos = jnp.arange(S)
        q = mm(x, w(a["wq"])).reshape(b, S, H, nope + r)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], -1)
        kv = mm(x, w(a["wkv_a"]))
        ckv = rms(kv[..., :c], a["kv_norm"]["scale"])
        kpe = rope(kv[..., None, c:], pos)
        kvb = mm(ckv, w(a["wkv_b"])).reshape(b, S, H, nope + dv)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(kpe, (b, S, H, r))], -1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(nope + r)
        causal = pos[:, None] >= pos[None, :]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(act)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, kvb[..., nope:], precision=prec)
        return mm(o.reshape(b, S, H * dv), w(a["wo"]))

    def experts(h, m):
        """The held experts' weighted sum, and every routed expert's choice
        score (score plus bias)."""
        scores = jax.nn.sigmoid(mm(h, w(m["router"])).astype(jnp.float32))
        choice = scores + m["router_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, K)
        wts = jnp.take_along_axis(scores, idx, axis=-1)
        wts = wts / wts.sum(-1, keepdims=True) * s["routed_scale"]
        # gate of each held expert for each token: its weight if chosen, else 0
        held = jnp.arange(first, first + E)
        gate = jnp.sum(wts[..., None] * (idx[..., None] == held), axis=-2)  # (b, S, E)
        up = jnp.einsum("bsd,edf->ebsf", h, w(m["wi"]), precision=prec)
        g = jnp.einsum("bsd,edf->ebsf", h, w(m["wg"]), precision=prec)
        hid = jax.nn.silu(g.astype(jnp.float32)).astype(act) * up
        hid = hid * jnp.moveaxis(gate, -1, 0)[..., None].astype(act)
        out = jnp.einsum("ebsf,efd->bsd", hid, w(m["wo"]), precision=prec)
        return out, choice

    def layer(x, p):
        """(x after the layer, the MoE layer's choice scores, or None)."""
        x = (x + attend(rms(x, p["ln1"]["scale"]), p["attn"])).astype(act)
        h = rms(x, p["ln2"]["scale"])
        if "moe" not in p:
            return (x + swiglu(h, p["mlp"])).astype(act), None
        out, choice = experts(h, p["moe"])
        return (x + out + swiglu(h, p["moe"]["shared"])).astype(act), choice

    def head(x, ln_f, head_w, idx):  # x (b, S, d); idx (P,) positions
        return mm(rms(x[:, idx], ln_f), w(head_w)).astype(jnp.float32)

    def embed(emb, tokens):
        return (emb.astype(jnp.float32) * math.sqrt(s["d_model"]))[tokens].astype(act)

    return jax.jit(layer), jax.jit(head), jax.jit(embed)


def held_margin(choice: np.ndarray, s: dict) -> np.ndarray:
    """How far the held expert nearest the top-K boundary lies from it, per
    token: ``choice`` (..., n_experts) are one MoE layer's choice scores
    (score plus bias). A held expert among the K best is that far above the
    (K+1)-th score, one outside them that far below the K-th: a rounding
    that moves the scores by less than half of it leaves the set of held
    experts chosen, and so this chip's routed sum, as it is."""
    K, first, held = s["top_k"], s["expert_first"], s["experts_held"]
    top = -np.sort(-choice, axis=-1)
    kth, next_ = top[..., K - 1:K], top[..., K:K + 1]
    mine = choice[..., first:first + held]
    return np.where(mine >= kth, mine - next_, kth - mine).min(axis=-1)


def reference_forward(params, s: dict, tokens: np.ndarray, positions: np.ndarray, *,
                      control: bool = False, block: int = 4):
    """(logits (n, P, vocab), margin (n, P)) at ``positions`` of each of the n
    token rows, computed layer by layer on blocks of ``block`` rows.

    ``margin`` is the position's smallest ``held_margin`` over the MoE
    layers (inf with none): where it is wide, a program that rounds its
    activations routes the position to the held experts the reference
    does, in every layer."""
    import jax

    layer, head, embed = _make_fns(s, control)
    tokens = np.asarray(tokens, np.int32)
    idx = np.asarray(positions, np.int32)
    stacks = [params[k] for k in ("dense_layers", "layers") if k in params]
    out, margins = [], []
    for lo in range(0, tokens.shape[0], block):
        x = embed(params["embedding"]["embed"], tokens[lo:lo + block])
        margin = np.full((x.shape[0], idx.size), np.inf, np.float32)
        for stack in stacks:
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                x, choice = layer(x, jax.tree.map(lambda a, i=i: a[i], stack))
                if choice is not None:
                    margin = np.minimum(margin, held_margin(np.asarray(choice)[:, idx], s))
        out.append(np.asarray(head(x, params["ln_f"]["scale"],
                                   params["embedding"]["head"], idx)))
        margins.append(margin)
        del x
    return np.concatenate(out), np.concatenate(margins)


def reference_logits(params, s: dict, tokens: np.ndarray, positions: np.ndarray, *,
                     control: bool = False, block: int = 4) -> np.ndarray:
    """Logits (n, P, vocab) float32 at ``positions`` of each of the n token
    rows, as a masked array: a position whose routing margin
    (``reference_forward``) is under ``s["route_margin"]`` is masked, since
    a program that rounds as ``torch_dtype`` does may route it otherwise.
    The control's logits are not masked: which positions count is the
    float32 reference's to say."""
    logits, margin = reference_forward(params, s, tokens, positions, control=control,
                                       block=block)
    if control:
        return logits
    open_ = np.broadcast_to((margin < s["route_margin"])[..., None], logits.shape)
    return np.ma.masked_array(logits, mask=open_)


def widest_gap(ref, chosen: np.ndarray) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best at that position, over the positions ``ref`` does
    not mask, or over all of them where it masks every one: a check that
    keeps no position would hold the program to nothing. ref: (n, P, V);
    chosen: (n, P)."""
    data = np.ma.getdata(ref)
    settled = ~np.ma.getmaskarray(ref)[..., 0]
    best = data.max(axis=-1)
    got = np.take_along_axis(data, chosen[..., None].astype(np.int64), axis=-1)[..., 0]
    gaps = best - got
    return float(gaps[settled].max() if settled.any() else gaps.max())
