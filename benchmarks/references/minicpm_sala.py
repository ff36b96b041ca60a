"""Plain reference of the ``minicpm_sala`` decoder stack: float32
``jax.numpy``, products at ``precision="highest"``, no cache, no chunks, no
kernels, nothing of the program. It follows the published ``config.json``
of MiniCPM-SALA (catalog row ``MiniCPM-SALA``: sparse ``minicpm4`` layers
and ``lightning-attn`` layers in one stack); what that file does not give
is the family's convention and is listed under **Assumed**.

``d`` = hidden size, ``L`` = the PUBLISHED number of layers (32) whatever
the cut; a row of T tokens at positions 0 .. T-1.

**Stack.** ``x0 = scale_emb * E[token]``. Each layer: ``h = x + s *
Mixer(RMS(x))``, ``y = h + s * MLP(RMS(h))``, ``s = scale_depth / sqrt(L)``;
``RMS`` has a learned scale, eps 1e-6; ``MLP(u) = W_down(silu(W_gate u) *
W_up u)``. Head: ``logits = W_head (RMS(x_L) / (hidden_size /
dim_model_base))``, untied.

**``minicpm4`` layer** (``u = RMS(x)``). ``q = W_q u`` as H heads of ``dh``,
``k, v = W_k u, W_v u`` as KV heads; RMS norm over the ``dh`` lanes of every
q head and every k head, each with one learned scale of ``dh``; no rotary;
scale ``dh^-1/2``; query heads ``G g .. G g + G - 1`` read key/value head
``g`` (``G = H / KV``). Selection for the query at position ``t``, per
key/value head ``g`` (kernel ``K``, stride ``St``, block ``B = 4 St``,
``K = 2 St``): compressed key ``Kc_j = mean(k[St j : St j + K])`` for every
``j`` with ``St j + K <= t + 1``; ``p_h = softmax_j(q_h . Kc_j dh^-1/2)``;
``s_g(j) = sum of p_h over the group's heads``; block score ``B(b) = max of
s_g(j) for j in 4b-1 .. 4b+3`` (those that exist); block 0 (``init_blocks``)
and the ``window / B`` blocks that end at t's own block score +inf; of the
blocks ``0 .. t // B`` the ``topk`` of largest score are selected (forced
ones inside the count, ties to the lower index); ``o_h = softmax over the
selected blocks' positions <= t of q_h . k dh^-1/2, times v``. With ``t <
topk B`` every block is selected: plain causal attention. Then ``o *
sigmoid(W_g u)`` elementwise over the joined heads and ``W_o``.

**``lightning-attn`` layer.** ``q, k, v = W_q u, W_k u, W_v u`` as H heads of
``dh``; the same RMS norm on q and k heads; rotary on q and k over all ``dh``
lanes, theta ``rope_theta``, lane ``i`` with ``i + dh/2``; per head ``h`` a
fixed decay ``lambda_h = exp(-2^(-8 (h + 1) / H))``; ``S_t = lambda_h S_(t-1)
+ k_t v_t^T`` (``dh x dh``), ``S_-1 = 0``; ``o_t = S_t^T q_t dh^-1/2``: a
``lax.scan`` over positions. RMS norm over the joined heads with a learned
scale, ``* sigmoid(W_g u)``, ``W_o``.

**Assumed** (the modelling code is not at hand; the configuration file
lists these under ``assumed`` too):

* ``mup_denominator`` is training-only;
* ``qk_norm``: one learned scale for q and one for k, shared by the heads,
  in both kinds of layer;
* ``sparse_config`` is not in the catalog's ``config``: kernel 32, stride
  16, block 64, one initial block, window 2048, top-k 64 are InfLLM-V2's as
  MiniCPM4 ships it (``described_as``: "block top-64"); forced blocks count
  inside the 64; ties go to the lower index;
* the output gates are elementwise over the joined heads, from the layer's
  normed input;
* the rotary pairs lane ``i`` with ``i + dh/2``;
* the decay is Lightning Attention-2's (arXiv:2401.04658, the ALiBi
  slopes), with no factor by layer and no activation on q, k, v;
* ``use_output_norm`` is one RMS over all joined heads.

**Departure.** The family's ``dense_len`` (attend densely when the whole
sequence is at most 8192) is not applied: selection is by position, so that
chunked prefill, decode and one full forward agree.

``precision`` "highest" is the reference. "bf16" rounds both operands of
every product to bfloat16 (what the configuration states), "fp8" to
float8_e4m3fn (the control, the step below). Norms, rotary, softmax,
selection scores, decay and the recurrent state are float32 either way.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


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


def sizes(cfg):
    """The numbers a layer needs, by the configuration's own keys."""
    sc = cfg["assumed"]["sparse_config"]
    h = cfg["lightning_nh"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        kv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        lh=h, ldh=cfg["lightning_head_dim"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        s=cfg["scale_depth"] / np.sqrt(cfg["published"]["num_hidden_layers"]),
        decay=np.exp(-2.0 ** (-8.0 * (np.arange(h) + 1) / h)).astype(
            np.float32),
        kernel=sc["kernel_size"], stride=sc["kernel_stride"],
        block=sc["block_size"], init=sc["init_blocks"],
        window=sc["window_size"] // sc["block_size"], topk=sc["topk"])


def layer_kinds(cfg):
    return list(cfg["mixer_types"])


def rope(x, pos, theta):
    """Rotary on the last axis of ``x (T, H, d)``: the pair ``(x[i], x[i +
    d/2])`` turns by ``pos * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-2.0 * np.arange(half) / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _blocks_of_rows(f, rows, args, block):
    """``f`` over blocks of ``block`` rows of each of ``args`` (padded at
    the end with the first row again), rows put together."""
    pad = -rows % block

    def cut(x):
        x = jnp.concatenate([x] + [x[:1]] * pad, 0) if pad else x
        return x.reshape((-1, block) + x.shape[1:])
    out = jax.lax.map(lambda a: f(*a), tuple(cut(x) for x in args))
    return out.reshape((-1,) + out.shape[2:])[:rows]


def selected_blocks(z, q, kc, t, n_blocks):
    """(B, KV, n_blocks) bool: the blocks the queries ``q (B, KV, G, dh)``
    at positions ``t (B,)`` select, from the compressed keys ``kc (J, KV,
    dh)`` (already rounded for a control)."""
    j = jnp.arange(kc.shape[0])
    s = jnp.einsum("bgid,jgd->bgij", q, kc, precision=HIGHEST) \
        * z["dh"] ** -0.5
    exists = (z["stride"] * j + z["kernel"])[None, :] <= (t + 1)[:, None]
    exists = exists[:, None, None, :]
    p = jnp.where(exists, jax.nn.softmax(jnp.where(exists, s, NEG), -1), 0.0)
    sg = jnp.sum(p, axis=2)                                  # (B, KV, J)
    b = jnp.arange(n_blocks)
    around = 4 * b[:, None] + jnp.arange(-1, 4)[None, :]     # (nb, 5)
    inside = (around >= 0) & (around < kc.shape[0])
    score = jnp.max(jnp.where(inside, sg[..., jnp.clip(
        around, 0, kc.shape[0] - 1)], 0.0), axis=-1)         # (B, KV, nb)
    own = (t // z["block"])[:, None]
    forced = (b[None, :] < z["init"]) | (b[None, :] > own - z["window"])
    candidate = b[None, :] <= own
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(candidate[:, None, :], score, -jnp.inf)
    # rank by falling score, ties to the lower index
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < z["topk"]) & candidate[:, None, :]


def _sparse_parts(z, p, u, precision):
    """Of a sparse layer's normed input ``u (T, D)``: the normed queries
    ``(T, KV, G, dh)`` and, rounded for a control, the keys and values
    ``(T, KV, dh)`` and the compressed keys ``(J, KV, dh)``."""
    t_len = u.shape[0]
    kv, g, dh = z["kv"], z["h"] // z["kv"], z["dh"]
    q = rms(_mm(u, p["att_q_weight"], precision).reshape(t_len, kv, g, dh),
            p["att_q_norm_gamma"], z["eps"])
    k = rms(_mm(u, p["att_k_weight"], precision).reshape(t_len, kv, dh),
            p["att_k_norm_gamma"], z["eps"])
    v = _mm(u, p["att_v_weight"], precision).reshape(t_len, kv, dh)
    n_kc = max((t_len - z["kernel"]) // z["stride"] + 1, 1)
    rows = z["stride"] * jnp.arange(n_kc)[:, None] \
        + jnp.arange(z["kernel"])[None, :]
    kc = jnp.mean(k[jnp.clip(rows, 0, t_len - 1)], axis=1)   # (J, KV, dh)
    return (q,) + tuple(_round(x, precision) for x in (k, v, kc))


def _attend_chosen(z, qb, tb, chosen, k_r, v_r, precision):
    """The queries ``qb (B, KV, G, dh)`` (rounded) at positions ``tb``
    over the positions ``<= tb`` of the blocks ``chosen (B, KV, n_blocks)``:
    ``(B, KV, G, dh)``."""
    t_len = k_r.shape[0]
    keep = (jnp.arange(t_len)[None, :] <= tb[:, None])[:, None, :] \
        & jnp.repeat(chosen, z["block"], axis=-1)[..., :t_len]  # (B, KV, T)
    s = jnp.einsum("bgid,sgd->bgis", qb, k_r, precision=HIGHEST) \
        * z["dh"] ** -0.5
    a = jax.nn.softmax(jnp.where(keep[:, :, None, :], s, NEG), axis=-1)
    return jnp.einsum("bgis,sgd->bgid", _round(a, precision), v_r,
                      precision=HIGHEST)


def sparse_attention(z, p, u, pos, precision, block=64):
    t_len = u.shape[0]
    q, k_r, v_r, kc_r = _sparse_parts(z, p, u, precision)
    n_blocks = -(-t_len // z["block"])

    def some(qb, tb):
        qb = _round(qb, precision)
        chosen = selected_blocks(z, qb, kc_r, tb, n_blocks)
        return _attend_chosen(z, qb, tb, chosen, k_r, v_r,
                              precision).reshape(qb.shape[0], -1)
    o = _blocks_of_rows(some, t_len, (q, pos), block)
    gate = jax.nn.sigmoid(_mm(u, p["att_gate_weight"], precision))
    return _mm(o * gate, p["att_o_weight"], precision)


def sparse_probe(cfg, p, x, at, blocks, precision="highest", block=64):
    """What a sparse layer selects and reads at the positions ``at (B,)``
    of the row whose layer input is ``x (T, D)``: ``(chosen (B, KV,
    n_blocks) bool, attended (B, H), own (B, H))``. ``chosen`` is the
    reference's own selection. ``attended`` is the mean over its lanes of
    each head's attention output when the blocks ``blocks (B, KV, K)`` are
    read (a program's own numbers, -1 where it names none) and not the
    reference's: what a program that selected those blocks has to have
    read out of them. ``own`` is the same under ``chosen``: how far the
    two lie apart is what the blocks selected otherwise move."""
    z = sizes(cfg)
    u = rms(x, p["ln1_gamma"], z["eps"])
    q, k_r, v_r, kc_r = _sparse_parts(z, p, u, precision)
    n_blocks = -(-x.shape[0] // z["block"])

    def some(qb, tb, named):
        qb = _round(qb, precision)
        chosen = selected_blocks(z, qb, kc_r, tb, n_blocks)
        given = jnp.any(named[..., None] == jnp.arange(n_blocks), axis=-2)
        return jnp.concatenate(
            [chosen.reshape(qb.shape[0], -1).astype(jnp.float32)] + [
                jnp.mean(_attend_chosen(z, qb, tb, which, k_r, v_r,
                                        precision), axis=-1).reshape(
                    qb.shape[0], -1) for which in (given, chosen)], axis=-1)
    out = _blocks_of_rows(some, at.shape[0], (q[at], at, blocks), block)
    n = z["kv"] * n_blocks
    return (out[:, :n].reshape(-1, z["kv"], n_blocks) > 0.5,
            out[:, n:n + z["h"]], out[:, n + z["h"]:])


def lightning_attention(z, p, u, pos, precision):
    t_len = u.shape[0]
    shape = (t_len, z["lh"], z["ldh"])
    q = rope(rms(_mm(u, p["att_q_weight"], precision).reshape(shape),
                 p["att_q_norm_gamma"], z["eps"]), pos, z["theta"])
    k = rope(rms(_mm(u, p["att_k_weight"], precision).reshape(shape),
                 p["att_k_norm_gamma"], z["eps"]), pos, z["theta"])
    v = _mm(u, p["att_v_weight"], precision).reshape(shape)
    q, k, v = (_round(x, precision) for x in (q, k, v))
    lam = jnp.asarray(z["decay"])[:, None, None]

    def one(state, qkv):
        qt, kt, vt = qkv                                     # (H, dh)
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.sum(state * qt[:, :, None], axis=1)
    _, o = jax.lax.scan(one, jnp.zeros(
        (z["lh"], z["ldh"], z["ldh"]), jnp.float32), (q, k, v))
    o = rms(o.reshape(t_len, -1) * z["ldh"] ** -0.5,
            p["att_out_norm_gamma"], z["eps"])
    gate = jax.nn.sigmoid(_mm(u, p["att_gate_weight"], precision))
    return _mm(o * gate, p["att_o_weight"], precision)


def gated_mlp(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def attention_half(cfg, kind, p, x, pos, precision="highest"):
    """``x + s * Mixer(RMS(x))`` of one layer on ``x (T, D)``, from the
    layer's ``ln1`` and ``att_*`` leaves."""
    z = sizes(cfg)
    u = rms(x, p["ln1_gamma"], z["eps"])
    mixer = sparse_attention if kind == SPARSE else lightning_attention
    return x + z["s"] * mixer(z, p, u, pos, precision)


def ffn_half(cfg, p, x, precision="highest", block=2048):
    """``x + s * MLP(RMS(x))``, from the layer's other leaves, in blocks of
    rows (a long row's hidden activations are what has to fit)."""
    z = sizes(cfg)

    def some(xb):
        return xb + z["s"] * gated_mlp(
            rms(xb, p["ln2_gamma"], z["eps"]), p["ffn_gate_weight"],
            p["ffn_up_weight"], p["ffn_down_weight"], precision)
    if x.shape[0] <= block:
        return some(x)
    return _blocks_of_rows(some, x.shape[0], (x,), block)


def attention_leaf(name):
    """Whether a layer's leaf belongs to :func:`attention_half`."""
    return name.startswith(("ln1_", "att_"))


def layer_params(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def embed(cfg, table, tokens):
    return cfg["scale_emb"] * table[tokens]


def logits(cfg, params, x, precision="highest"):
    h = rms(x, params["final_ln_gamma"], cfg["rms_norm_eps"]) \
        / (cfg["hidden_size"] / cfg["dim_model_base"])
    return _mm(h, params["lm_head_weight"], precision)


def forward(cfg, params, tokens, precision="highest"):
    """Logits (T, vocabulary) of one row of token ids, the whole model at
    once: what the tests at small sizes compare with."""
    pos = jnp.arange(tokens.shape[0])
    x = embed(cfg, params["tok_embed_weight"], tokens)
    for i, kind in enumerate(layer_kinds(cfg)):
        p = layer_params(params, i)
        x = ffn_half(cfg, p, attention_half(cfg, kind, p, x, pos, precision),
                     precision)
    return logits(cfg, params, x, precision)


def make_halves(cfg, kind, precision="highest"):
    """The two halves of a layer as compiled programs, one a (kind of
    layer, length): jitted ``(p, x, pos) -> x`` and ``(p, x) -> x``. The
    benchmark's streamed reference calls them layer by layer, each with
    its own leaves alone on the device."""
    return (jax.jit(functools.partial(attention_half, cfg, kind,
                                      precision=precision)),
            jax.jit(functools.partial(ffn_half, cfg, precision=precision)))


def make_probe(cfg, precision="highest"):
    """Jitted :func:`sparse_probe`: ``(a sparse layer's attention leaves, x
    (T, D), at (B,), blocks (B, KV, K)) -> (chosen, attended, own)``."""
    return jax.jit(functools.partial(sparse_probe, cfg, precision=precision))


def make_logits(cfg, precision="highest"):
    """Jitted ``(params with the final norm and the head, x (K, D)) ->
    logits (K, vocabulary)``."""
    return jax.jit(functools.partial(logits, cfg, precision=precision))
