"""Plain reference of the ``sarvam_mla`` decoder layer: float32
``jax.numpy``, products at ``precision="highest"``, no cache, no kernels,
nothing of the program. It follows the published ``config.json`` of
sarvam-105b (latent attention without a query latent, YaRN rotary, sigmoid
routing with an expert bias over 128 experts beside one shared expert).

One layer on a row of T tokens (pre-norm residual: ``x += Attn(RMS(x))``,
``x += FFN(RMS(x))``; ``RMS`` has a learned scale, eps 1e-6; ``h = RMS(x)``):

* MLA. ``q = W_q h`` as H heads of ``[q_nope | q_rope]``. ``[c_kv |
  k_rope] = W_kva h``, ``c_kv <- RMS(c_kv)``; rotary on ``q_rope`` of every
  head and on ``k_rope`` (one a token, shared by the heads). ``[k_nope_h |
  v_h] = W_kvb,h c_kv``. ``score_h(t, s) = sigma (q_nope_h . k_nope_h(s) +
  q_rope_h . k_rope(s))`` for ``s <= t``, ``sigma = (d_nope + d_rope)^-1/2
  m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``; softmax over ``s``;
  ``attn = W_o [o_1 .. o_H]``. Dense and per head: the absorbed form is the
  program's, not the reference's.
* Rotary, ``deepseek_yarn`` over the ``d`` rotary lanes: ``f_i =
  theta^(-2i/d)``; ``c(r) = d ln(L / (2 pi r)) / (2 ln theta)`` with ``L``
  the original length; ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``, clipped to ``[0, d - 1]``; ``g_i = clip((i - low) /
  (high - low), 0, 1)``; pair ``i`` turns at ``f_i (1 - g_i) + (f_i /
  factor) g_i``; cos and sin are scaled by ``mscale / mscale_all_dim``'s
  two factors' ratio (1 here).
* FFN. Dense: ``W_down(silu(W_gate h) * W_up h)``. Sparse: ``s =
  sigmoid(W_r h)`` (float32); the ``num_experts_per_tok`` experts of
  largest ``s_e + b_e``; ``g_e = routed_scaling_factor s_e / sum_chosen
  s``; ``y = sum over the chosen experts held here of g_e E_e(h), +
  E_shared(h)``, every held expert applied to every token, one after another.
* Final ``RMS``, head over the rows held, untied, no bias.

Assumed (the modelling code is not at hand; the configuration file lists
these under ``assumed`` too). None changes a shape, a byte or the absorbed
decode:

* ``use_qk_norm`` is read as the ``RMS`` on ``c_kv`` above (the norm every
  MLA of this family has; a norm on the expanded per-head key would forbid
  the absorbed form that ``head_dim`` 576 = 512 + 64 declares) and one
  ``RMS`` over each head's whole ``d_nope + d_rope`` query before the
  rotary, a learned scale of that width shared by the heads; ``k_rope`` is
  not normalised;
* the chosen scores are normalised to sum 1 before the factor 2.5
  (``norm_topk_prob``, the family's convention); one group of experts (the
  config names none);
* the rotary pairs lane ``i`` with lane ``i + d/2`` (with weights from a
  seed the interleaved form is a permutation of it).

Departures:

* what the experts that are not held would add is left out: the reference
  is given the same share of the deployment as the program
  (``deployment.expert_first``, ``num_experts`` held of
  ``published.num_experts`` routed);
* the vocabulary is the slice held (``vocab_held`` rows).

``precision`` "highest" is the reference. "fp8" is the control: the same
mathematics with both operands of every product rounded to float8_e4m3fn,
the step below the bfloat16 the configuration computes in. The router
works in float32 either way.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


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


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg):
    """(the pairs' frequencies (d/2,), the factor on cos and sin, the
    softmax scale sigma)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    assert y["type"] == "deepseek_yarn"
    length = y["original_max_position_embeddings"]
    f = theta ** (-2.0 * np.arange(d // 2) / d)

    def c(r):
        return d * math.log(length / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(c(y["beta_fast"])), 0)
    high = min(math.ceil(c(y["beta_slow"])), d - 1)
    g = np.clip((np.arange(d // 2) - low) / float(high - low), 0.0, 1.0)
    freq = f * (1 - g) + f / y["factor"] * g
    m = _mscale(y["factor"], y["mscale_all_dim"])
    sigma = (cfg["qk_nope_head_dim"] + d) ** -0.5 * m * m
    return freq.astype(np.float32), _mscale(y["factor"], y["mscale"]) / m, \
        sigma


def rope(x, pos, freq, on_angles):
    """Rotary on the last axis of ``x (T, ..., d)``: the pair ``(x[i],
    x[i + d/2])`` turns by ``pos * freq[i]``."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * on_angles).reshape(shape)
    sin = (jnp.sin(ang) * on_angles).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def sizes(cfg):
    """The numbers a layer needs, by the configuration's own keys."""
    freq, on_angles, sigma = yarn(cfg)
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], rkv=cfg["kv_lora_rank"],
        eps=cfg["rms_norm_eps"], freq=freq, on_angles=on_angles,
        sigma=sigma, routed=cfg["published"]["num_experts"],
        held=cfg["num_experts"], first=cfg["deployment"]["expert_first"],
        per_tok=cfg["num_experts_per_tok"],
        scaling=cfg["routed_scaling_factor"])


def layer_kinds(cfg):
    dense = cfg["first_k_dense_replace"]
    return ["dense"] * dense + ["sparse"] * (cfg["num_hidden_layers"] - dense)


def attention(z, p, h, pos, precision, block=128):
    """Per-head MLA under the causal mask, in blocks of queries (a block's
    scores are what has to fit, not the row's)."""
    t = h.shape[0]
    q = rms(_mm(h, p["att_q_weight"], precision).reshape(
        t, z["h"], z["dn"] + z["dr"]), p["att_q_norm_gamma"], z["eps"])
    q = jnp.concatenate([q[..., :z["dn"]], rope(
        q[..., z["dn"]:], pos, z["freq"], z["on_angles"])], -1)
    kva = _mm(h, p["att_kva_weight"], precision)
    c_kv = rms(kva[:, :z["rkv"]], p["att_kva_norm_gamma"], z["eps"])
    k_rope = _round(rope(kva[:, z["rkv"]:], pos, z["freq"], z["on_angles"]),
                    precision)
    # a head's rows of W_kvb: its keys' nope part, then its values
    w_kvb = p["att_kvb_weight"].reshape(z["h"], z["dn"] + z["dv"], z["rkv"])
    c_r = _round(c_kv, precision)
    k_nope = _round(jnp.einsum("sr,hdr->shd", c_r, _round(
        w_kvb[:, :z["dn"]], precision), precision=HIGHEST), precision)
    v = _round(jnp.einsum("sr,hdr->shd", c_r, _round(
        w_kvb[:, z["dn"]:], precision), precision=HIGHEST), precision)

    def rows(args):
        qb, pb = args                               # (B, H, .), (B,)
        causal = pos[None, :] <= pb[:, None]
        qb = _round(qb, precision)
        s = (jnp.einsum("thd,shd->hts", qb[..., :z["dn"]], k_nope,
                        precision=HIGHEST)
             + jnp.einsum("thd,sd->hts", qb[..., z["dn"]:], k_rope,
                          precision=HIGHEST)) * z["sigma"]
        a = jax.nn.softmax(jnp.where(causal[None], s, NEG), axis=-1)
        return jnp.einsum("hts,shd->thd", _round(a, precision), v,
                          precision=HIGHEST)

    # a padded query row stands at position 0 and sees key 0 alone; its
    # output is cut off below
    pad = -t % block

    def cut(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, block) + x.shape[1:])
    o = jax.lax.map(rows, (cut(q), cut(pos)))
    o = o.reshape(-1, z["h"] * z["dv"])[:t]
    return _mm(o, p["att_o_weight"], precision)


def gated_mlp(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def route(z, p, h):
    """(T, routed) gates: ``g_e`` on the chosen experts, 0 elsewhere.
    The router works in float32 whatever the control rounds."""
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", h, p["router_weight"],
                                  precision=HIGHEST))
    top = jax.lax.top_k(s + p["router_bias"], z["per_tok"])[1]  # (T, k)
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None],
                                  top].set(1.0)
    chosen = s * picked
    return z["scaling"] * chosen / jnp.sum(chosen, -1, keepdims=True)


def experts(z, p, h, precision, first=None, held=None):
    """The share of the routed result that the experts ``first .. first +
    held`` give (``p["experts_*"]`` holds just those, each matrix lying
    (in, out)), without the shared expert: every expert held applied to
    every token, weighed by its gate (0 where the token did not choose
    it)."""
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    g = route(z, p, h)[:, first:first + held]

    def add_one(y, expert):
        w_gate, w_up, w_down, gate = expert
        return y + gate[:, None] * gated_mlp(h, w_gate.T, w_up.T, w_down.T,
                                             precision), None
    # one expert after another (a scan, so that sixteen experts compile
    # as one)
    y, _ = jax.lax.scan(add_one, jnp.zeros_like(h), (
        p["experts_gate_weight"], p["experts_up_weight"],
        p["experts_down_weight"], g.T))
    return y


def ffn(z, p, h, mlp_type, precision):
    if mlp_type == "dense":
        return gated_mlp(h, p["ffn_gate_weight"], p["ffn_up_weight"],
                         p["ffn_down_weight"], precision)
    return experts(z, p, h, precision) + gated_mlp(
        h, p["shared_gate_weight"], p["shared_up_weight"],
        p["shared_down_weight"], precision)


def attention_half(cfg, p, x, pos, precision="highest"):
    """``x + attention(RMS(x))`` of one block on ``x (T, D)``, from the
    block's ``ln1`` and ``att_*`` leaves."""
    z = sizes(cfg)
    return x + attention(z, p, rms(x, p["ln1_gamma"], z["eps"]), pos,
                         precision)


def ffn_half(cfg, mlp_type, p, x, precision="highest"):
    """``x + FFN(RMS(x))``, from the block's other leaves."""
    z = sizes(cfg)
    return x + ffn(z, p, rms(x, p["ln2_gamma"], z["eps"]), mlp_type,
                   precision)


def layer(cfg, mlp_type, p, x, pos, precision="highest"):
    """One block: its two halves, one after the other."""
    return ffn_half(cfg, mlp_type, p,
                    attention_half(cfg, p, x, pos, precision), precision)


def attention_leaf(name):
    """Whether a layer's leaf belongs to :func:`attention_half`."""
    return name.startswith(("ln1_", "att_"))


def layer_params(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def logits(cfg, params, x, precision="highest"):
    return _mm(rms(x, params["final_ln_gamma"], cfg["rms_norm_eps"]),
               params["lm_head_weight"], precision)


def forward(cfg, params, tokens, precision="highest"):
    """Logits (T, vocab held) of one row of token ids, the whole model at
    once: what the tests at small sizes compare with."""
    pos = jnp.arange(tokens.shape[0])
    x = params["tok_embed_weight"][tokens]
    for i, mlp_type in enumerate(layer_kinds(cfg)):
        x = layer(cfg, mlp_type, layer_params(params, i), x, pos, precision)
    return logits(cfg, params, x, precision)


def make_halves(cfg, mlp_type, precision="highest"):
    """The two halves of a block as compiled programs, one a (kind of
    layer, length): jitted ``(p, x, pos) -> x`` and ``(p, x) -> x``. The
    benchmark's streamed reference calls them layer by layer, each with
    its own leaves alone on the device."""
    return (jax.jit(functools.partial(attention_half, cfg,
                                      precision=precision)),
            jax.jit(functools.partial(ffn_half, cfg, mlp_type,
                                      precision=precision)))


def make_logits(cfg, precision="highest"):
    """Jitted ``(params with the final norm and the head, x (K, D)) ->
    logits (K, vocab held)``."""
    return jax.jit(functools.partial(logits, cfg, precision=precision))
