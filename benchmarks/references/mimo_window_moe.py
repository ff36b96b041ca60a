"""Plain reference of the ``mimo_v2_flash`` decoder stack: float32
``jax.numpy``, products at ``precision="highest"``, no cache, no chunks, no
kernels, nothing of the program. It follows the published ``config.json``
of MiMo-V2-Flash (catalog row ``MiMo-V2-Flash``: full and sliding-window
grouped-query attention layers, sigmoid-routed experts); what that file
does not give is listed under **Assumed**.

A row of T tokens at positions 0 .. T-1. Each layer is two residual steps,
RMS norms at ``layernorm_epsilon`` 1e-5: ``x += Attn(RMS(x))``, ``x +=
FFN(RMS(x))``; then a final RMS and the head over the rows held.

**Attention.** ``H_kv`` = ``num_key_value_heads`` (4) on a full layer,
``swa_num_key_value_heads`` (8) on a window layer. ``q = W_q h`` gives 64
heads of 192, ``k = W_k h`` ``H_kv`` heads of 192, ``v = 0.707 W_v h``
``H_kv`` heads of 128 (``attention_value_scale``). Rotary
(``partial_rotary_factor`` 0.334): the first 64 of the 192 lanes of every
q and k head, lane ``i`` turned with ``i + 32`` at ``theta^(-2i/64)``,
theta ``rope_theta`` 5e6 on full layers and ``swa_rope_theta`` 1e4 on
window layers. Scores ``q . k / sqrt(192)``, query head ``g`` reading
key/value head ``g // (64 / H_kv)``. Full layers are causal. Window layers
see key ``j`` from query ``t`` iff ``t - 128 < j <= t``, and their softmax
takes a learned logit ``s_h`` a head into the denominator only: ``p_j =
e^{z_j} / (e^{s_h} + sum_j e^{z_j})``. Output ``W_o`` over the 64 joined
heads of 128.

**FFN.** Layer 0: ``W_down(silu(W_gate h) * W_up h)``, 16384 wide. Later
layers: ``s = sigmoid(W_r h)`` over 256 experts in float32; the top 8 by
``s + b``; gates ``s / sum_chosen s`` times 1.0 (``routed_scaling_factor``
null); ``y = sum over the chosen experts held here of g_e E_e(h)``, each a
SiLU-gated MLP 2048 wide, every held expert applied to every token; no
shared expert.

**Assumed** (the modelling code is not at hand; the configuration file
lists these under ``assumed`` too): the norm is RMS with a learned scale;
a 0 in ``hybrid_layer_pattern`` is a full layer; the rotary lanes and
their pairing as above; the window inclusive of the query's own position
(128 keys); ``attention_chunk_size`` an implementation chunk that changes
no mask; the sink on window layers only; the value scale on ``v`` (linear:
scaling ``v`` or the output is the same).

**Departures.** No multi-token prediction. What the experts that are not
held would add is left out, and the vocabulary is the slice held: the
reference is given the same share of the deployment as the program.

``precision`` "highest" is the reference; "bf16" rounds both operands of
every product to bfloat16 (what the configuration states), "fp8" to
float8_e4m3fn (the control, the step below). Norms, rotary, router,
softmax and the sink are float32 either way.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST
# ``drivers/serve_longdoc.reference_logits`` probes the layers of this kind;
# this stack selects nothing, so none is
SPARSE = None


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _mm(a, w, precision):
    """a @ w.T in float32, operands rounded for a control."""
    return jnp.einsum("...k,nk->...n", _round(a, precision),
                      _round(w, precision), precision=HIGHEST)


def rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


def layer_kinds(cfg):
    """``(attention, ffn)`` of each layer held: published layers 0 ..
    num_hidden_layers - 1."""
    n = cfg["num_hidden_layers"]
    return [("window" if a else "full", "moe" if f else "dense")
            for a, f in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def sizes(cfg, attn):
    """The numbers one kind of attention layer needs."""
    pre = "swa_" if attn == "window" else ""
    dk = cfg[pre + "head_dim"]
    rot = int(cfg["partial_rotary_factor"] * dk) // 2 * 2
    theta = float(cfg["swa_rope_theta"] if attn == "window"
                  else cfg["rope_theta"])
    return dict(h=cfg[pre + "num_attention_heads"],
                kv=cfg[pre + "num_key_value_heads"], dk=dk,
                dv=cfg[pre + "v_head_dim"], rot=rot,
                freq=(theta ** (-np.arange(0, rot, 2) / rot)).astype(
                    np.float32),
                window=cfg["sliding_window"] if attn == "window" else None,
                eps=cfg["layernorm_epsilon"],
                value_scale=cfg["attention_value_scale"])


def rope(x, pos, z):
    """Rotary on the first ``rot`` lanes of ``x (T, heads, d)``: the pair
    ``(x[i], x[i + rot/2])`` turns by ``pos * freq[i]``."""
    half = z["rot"] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(z["freq"])[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:z["rot"]]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., z["rot"]:]], -1)


def attention(z, p, h, pos, precision, block=64):
    """Grouped-query attention of one layer over the row, in blocks of
    queries (a block's scores are what has to fit, not the row's). A
    window layer's block reads the block's own keys and the ``window``
    before them; a full layer's every key, under the causal mask."""
    t = h.shape[0]
    q = rope(_mm(h, p["att_q_weight"], precision).reshape(
        t, z["h"], z["dk"]), pos, z)
    k = _round(rope(_mm(h, p["att_k_weight"], precision).reshape(
        t, z["kv"], z["dk"]), pos, z), precision)
    v = _round(z["value_scale"] * _mm(h, p["att_v_weight"], precision)
               .reshape(t, z["kv"], z["dv"]), precision)
    group = z["h"] // z["kv"]
    # query head g reads key/value head g // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    sink = p.get("att_sink")
    pad = -t % block
    w = z["window"]
    if w is not None:
        # keys W positions ahead of the row: a block of queries starting
        # at b0 reads rows b0 .. b0 + block + W of the padded keys
        k = jnp.pad(k, ((w, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((w, pad), (0, 0), (0, 0)))
        k_pos = jnp.arange(-w, t + pad)

    def rows(args):
        qb, pb, b0 = args                           # (B, H, dk), (B,), ()
        if w is None:
            kb, vb, kp = k, v, pos
            keep = kp[None, :] <= pb[:, None]
        else:
            n = block + w
            kb = jax.lax.dynamic_slice_in_dim(k, b0, n)
            vb = jax.lax.dynamic_slice_in_dim(v, b0, n)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, b0, n)
            keep = (kp[None, :] <= pb[:, None]) \
                & (kp[None, :] > pb[:, None] - w) & (kp[None, :] >= 0)
        s = jnp.einsum("bhd,shd->hbs", _round(qb, precision), kb,
                       precision=HIGHEST) / np.sqrt(z["dk"])
        s = jnp.where(keep[None], s, NEG)
        m = jnp.max(s, -1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, None, None])
        e = jnp.where(keep[None], jnp.exp(s - m), 0.0)
        den = jnp.sum(e, -1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sink[:, None, None] - m)
        a = e / den
        return jnp.einsum("hbs,shd->bhd", _round(a, precision), vb,
                          precision=HIGHEST)

    # a padded query row stands past the row's end; its output is cut off
    def cut(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, block) + x.shape[1:])
    starts = jnp.arange(0, t + pad, block)
    o = jax.lax.map(rows, (cut(q), cut(pos), starts))
    o = o.reshape(-1, z["h"] * z["dv"])[:t]
    return _mm(o, p["att_o_weight"], precision)


def gated_mlp(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def route(cfg, p, h):
    """(T, routed) gates: ``g_e`` on the chosen experts, 0 elsewhere.
    The router works in float32 whatever the control rounds."""
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", h, p["router_weight"],
                                  precision=HIGHEST))
    top = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])[1]
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None],
                                  top].set(1.0)
    chosen = s * picked
    scaling = cfg["routed_scaling_factor"]
    return (1.0 if scaling is None else scaling) * chosen \
        / jnp.sum(chosen, -1, keepdims=True)


def experts(cfg, p, h, precision, first=None, held=None):
    """The share of the routed result that the experts ``first .. first +
    held`` give (``p["experts_*"]`` holds just those, each matrix lying
    (in, out)): every expert held applied to every token, weighed by its
    gate (0 where the token did not choose it)."""
    first = cfg["deployment"]["expert_first"] if first is None else first
    held = cfg["n_routed_experts"] if held is None else held
    g = route(cfg, p, h)[:, first:first + held]

    def add_one(y, expert):
        w_gate, w_up, w_down, gate = expert
        return y + gate[:, None] * gated_mlp(h, w_gate.T, w_up.T, w_down.T,
                                             precision), None
    y, _ = jax.lax.scan(add_one, jnp.zeros_like(h), (
        p["experts_gate_weight"], p["experts_up_weight"],
        p["experts_down_weight"], g.T))
    return y


def ffn(cfg, ffn_kind, p, h, precision):
    if ffn_kind == "dense":
        return gated_mlp(h, p["ffn_gate_weight"], p["ffn_up_weight"],
                         p["ffn_down_weight"], precision)
    return experts(cfg, p, h, precision)


def attention_half(cfg, kind, p, x, pos, precision="highest"):
    """``x + Attn(RMS(x))`` of one layer on ``x (T, D)``, from its ``ln1``
    and ``att_*`` leaves."""
    z = sizes(cfg, kind[0])
    return x + attention(z, p, rms(x, p["ln1_gamma"], z["eps"]), pos,
                         precision)


def ffn_half(cfg, kind, p, x, precision="highest", block=2048):
    """``x + FFN(RMS(x))``, from the layer's other leaves, in blocks of
    rows."""
    eps = cfg["layernorm_epsilon"]

    def some(xb):
        return xb + ffn(cfg, kind[1], p, rms(xb, p["ln2_gamma"], eps),
                        precision)
    t = x.shape[0]
    if t <= block:
        return some(x)
    pad = -t % block
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    return jax.lax.map(some, xs).reshape(-1, x.shape[1])[:t]


def attention_leaf(name):
    """Whether a layer's leaf belongs to :func:`attention_half`."""
    return name.startswith(("ln1_", "att_"))


def layer_params(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def embed(cfg, table, tokens):
    return table[tokens]


def logits(cfg, params, x, precision="highest"):
    return _mm(rms(x, params["final_ln_gamma"], cfg["layernorm_epsilon"]),
               params["lm_head_weight"], precision)


def forward(cfg, params, tokens, precision="highest"):
    """Logits (T, vocab held) of one row of token ids, the whole model at
    once: what the tests at small sizes compare with."""
    pos = jnp.arange(tokens.shape[0])
    x = embed(cfg, params["tok_embed_weight"], tokens)
    for i, kind in enumerate(layer_kinds(cfg)):
        p = layer_params(params, i)
        x = ffn_half(cfg, kind, p, attention_half(cfg, kind, p, x, pos,
                                                  precision), precision)
    return logits(cfg, params, x, precision)


def make_halves(cfg, kind, precision="highest"):
    """The two halves of a layer as compiled programs, one a (kind of
    layer, length): jitted ``(p, x, pos) -> x`` and ``(p, x) -> x``. The
    benchmark's streamed reference calls them layer by layer, each with
    its own leaves alone on the device."""
    return (jax.jit(functools.partial(attention_half, cfg, kind,
                                      precision=precision)),
            jax.jit(functools.partial(ffn_half, cfg, kind,
                                      precision=precision)))


def make_logits(cfg, precision="highest"):
    """Jitted ``(params with the final norm and the head, x (K, D)) ->
    logits (K, vocab held)``."""
    return jax.jit(functools.partial(logits, cfg, precision=precision))
