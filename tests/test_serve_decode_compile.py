"""The decode program, compiled for the chip it serves on (ISSUE 26).

No chip is attached here: the TPU's compiler compiles for a DESCRIBED
``v5e:2x2`` device, at the serving cell's widths (2048 wide, 32 heads of
64, FFN 8192, vocabulary 50272, 16 slots of 2048 positions) with two
layers for eight. What is asserted is what the compiler decided about
the KV cache, which no CPU test can see: that the donated cache is
updated where it lies, and that no step moves a layer's whole slab —
the program this guards against read 50 GB a step whatever the bucket.
Nothing here is a time: a compile that passes is not a chip run.

ISSUE 28 parted the engine into a generic half and the families it serves.
The dense decoder's programs are pinned to the bytes they cost before
that, so that ``opt13_serve_chat`` cannot drift; and the second family's
decode step (latent attention over a latent plane, routed experts) is
compiled at the published widths, where the compiler keeps a 576-wide
latent plane positions-minor and copies all of it around every append
(found by PR 27): rows are stored 640 wide.

ISSUE 29 chose the greedy token inside the programs: each returns the
int32 argmax beside the float32 logits it was taken over. The pinned
bytes moved by what the compiler's cost model charges for it, and the
cache is still aliased whole.
"""
import re
import types

import numpy as np
import pytest

LAYERS, SLOTS, HEADS, D_HEAD, D_FF, VOCAB, MAX_SEQ = 2, 16, 32, 64, 8192, \
    50272, 2048
D_MODEL = HEADS * D_HEAD
PAGE = 16


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # an entry written for a described device cannot be read back
    # without one: keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(one_chip):
    """(params, state) of the cell as shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"tok_embed_weight": sd(VOCAB, D_MODEL),
              "pos_embed_weight": sd(MAX_SEQ, D_MODEL),
              "lm_head_weight": sd(VOCAB, D_MODEL),
              "lm_head_bias": sd(VOCAB),
              "final_ln_gamma": sd(D_MODEL), "final_ln_beta": sd(D_MODEL)}
    for li in range(LAYERS):
        pfx = "layer%d_" % li
        for ln in ("ln1", "ln2"):
            params[pfx + ln + "_gamma"] = sd(D_MODEL)
            params[pfx + ln + "_beta"] = sd(D_MODEL)
        for fc, (n_out, n_in) in (("att_qkv", (3 * D_MODEL, D_MODEL)),
                                  ("att_proj", (D_MODEL, D_MODEL)),
                                  ("ff1", (D_FF, D_MODEL)),
                                  ("ff2", (D_MODEL, D_FF))):
            params[pfx + fc + "_weight"] = sd(n_out, n_in)
            params[pfx + fc + "_bias"] = sd(n_out)
    return params, sd


def _state(sd, int8=False):
    """The cache's state tuple as ``KVCache`` lays it out."""
    import jax.numpy as jnp
    slab = (LAYERS, SLOTS, MAX_SEQ, D_MODEL)
    if not int8:
        return (sd(*slab), sd(*slab))
    scales = (LAYERS, SLOTS, HEADS, MAX_SEQ // PAGE)
    return (sd(*slab, dtype=jnp.int8), sd(*slab, dtype=jnp.int8),
            sd(*scales), sd(*scales))


def _nbytes(tree):
    import jax
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


@pytest.fixture
def for_the_chip(monkeypatch):
    """The default backend here is the CPU, where a Pallas kernel runs
    interpreted: these compiles take the chip's branch instead."""
    from mxnet_tpu import rtc
    monkeypatch.setattr(rtc, "resolve_interpret", lambda arrays: False)


def _dense_engine(one_chip, bucket, int8):
    """The dense decoder's engine over the cell's shapes: (engine,
    params, state, sd)."""
    from mxnet_tpu.serve.decode import DecodeEngine, DenseDecoder
    params, sd = _shapes(one_chip)
    cache = types.SimpleNamespace(int8=int8, page=PAGE, max_seq=MAX_SEQ,
                                  max_slots=SLOTS, _sharding=None)
    eng = DecodeEngine(DenseDecoder(params, HEADS), cache, None,
                       seq_buckets=[bucket])
    return eng, params, _state(sd, int8), sd


def _compile_decode(one_chip, s_b, int8):
    import jax.numpy as jnp
    eng, params, state, sd = _dense_engine(one_chip, s_b, int8)
    compiled = eng.family.build_decode(s_b).lower(
        params, state, sd(SLOTS, dtype=jnp.int32),
        sd(SLOTS, dtype=jnp.int32), sd(SLOTS, dtype=jnp.bool_)).compile()
    return compiled, params, state


def _compile_prefill(one_chip, t_b, int8=False):
    import jax.numpy as jnp
    eng, params, state, sd = _dense_engine(one_chip, t_b, int8)
    compiled = eng.family.build_prefill(t_b).lower(
        params, state, sd(t_b, dtype=jnp.int32), sd(dtype=jnp.int32),
        sd(dtype=jnp.int32)).compile()
    return compiled, params, state


_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = \(?(\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\((.*)$", re.M)
_MAKES_NO_DATA = ("parameter", "get-tuple-element", "tuple", "bitcast")


def _slab_makers(hlo, slab_elems):
    """Instructions of the entry computation whose result holds a whole
    layer's slab or more and that are not an in-place update fusion:
    (name, op) pairs."""
    roots = {}
    for m in re.finditer(r"^%(\S+) \(.*?\n((?:  .*\n)+?)\}", hlo, re.M):
        root = re.search(r"^\s+ROOT %\S+ = \S+ ([\w\-]+)\(", m.group(2),
                         re.M)
        roots[m.group(1)] = root.group(1) if root else None
    entry = hlo[hlo.index("\nENTRY"):]
    out = []
    for name, _dt, dims, op, rest in _INSTR.findall(entry):
        elems = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        if elems < slab_elems or op in _MAKES_NO_DATA:
            continue
        if op == "fusion":
            called = re.search(r"calls=%([\w\.\-]+)", rest)
            if called and roots.get(called.group(1)) in (
                    "scatter", "dynamic-update-slice"):
                continue
        out.append((name, op))
    return out


@pytest.mark.parametrize("int8,s_b", [(False, 512), (False, 1536),
                                      (True, 512)],
                         ids=["f32-512", "f32-1536", "int8-512"])
def test_decode_step_updates_the_cache_in_place(one_chip, for_the_chip,
                                                int8, s_b):
    compiled, params, state = _compile_decode(one_chip, s_b, int8)
    mem = compiled.memory_analysis()
    # the donated cache is the output cache: aliased whole
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 400e6, mem.temp_size_in_bytes
    moved = _slab_makers(compiled.as_text(), SLOTS * MAX_SEQ * D_MODEL)
    assert not moved, "whole-slab instructions: %r" % moved
    if int8:        # the XLA read moves the bucket dequantized: no bound
        return
    # what a step may read: every weight, and the bucket's keys and
    # values a few times over — never the slabs, whose bytes do not
    # depend on the bucket
    read = compiled.cost_analysis()["bytes accessed"]
    allowed = _nbytes(params) + 5 * _nbytes(state) * s_b // MAX_SEQ
    assert read < allowed, (read, allowed)


def test_prefill_writes_its_rows_in_place(one_chip):
    """The prefill program at the median prompt's bucket: the cache is
    aliased whole, each layer's K and V block lands by an in-place
    update, and the rows reach it without a copy of their own (K and V
    sliced out of one fused projection were strided copies, and cost
    three layers' FFN fusions their tiling at this bucket)."""
    t_b = 256
    compiled, _params, state = _compile_prefill(one_chip, t_b)
    assert compiled.memory_analysis().alias_size_in_bytes == _nbytes(state)
    hlo = compiled.as_text()
    assert not _slab_makers(hlo, SLOTS * MAX_SEQ * D_MODEL)
    entry = hlo[hlo.index("\nENTRY"):]
    row_copies = [name for name, _dt, dims, op, _rest in _INSTR.findall(entry)
                  if op == "copy" and dims == "1,1,%d,%d" % (t_b, D_MODEL)]
    assert not row_copies, row_copies


@pytest.mark.parametrize("s_b", [128, 1536])
def test_decode_attention_kernel_compiles_at_real_widths(one_chip,
                                                         for_the_chip, s_b):
    """The kernel alone, through Mosaic: the whole cache goes in as it
    lies (no operand copy, next to no temporaries) and the call is
    there."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.decode_attention import (block_for,
                                                       decode_attention,
                                                       fetch_plan)
    _params, sd = _shapes(one_chip)

    def attend(q, k, v, pos, active):
        plan = fetch_plan(pos, active, block_for(s_b))
        return decode_attention(q, k, v, LAYERS - 1, plan, n_heads=HEADS,
                                bucket=s_b)

    compiled = jax.jit(attend).lower(
        sd(SLOTS, D_MODEL), *_state(sd), sd(SLOTS, dtype=jnp.int32),
        sd(SLOTS, dtype=jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


# bytes accessed: as the parent of ISSUE 28 compiled these programs (the
# engine before it was parted; read there with this file's own helpers),
# and since ISSUE 29 chose the token inside them. The compiler's cost
# model charges the argmax five times the logits' bytes, not the one read
# it is: it now makes the logits in fast memory, and counts their copy
# out to the program's output and the reduce's operands beside the read.
_BYTES_ACCESSED = {("decode", False, 512): (1676829696, 1692933120),
                   ("decode", False, 1536): (1676829696, 1692933120),
                   ("decode", True, 512): (2855140864, 2871244288),
                   ("prefill", False, 256): (1812728832, 1813758976)}


@pytest.mark.parametrize("kind,int8,bucket", sorted(_BYTES_ACCESSED))
def test_dense_programs_cost_what_they_cost_before_and_the_argmax(
        one_chip, for_the_chip, kind, int8, bucket):
    compile_ = _compile_decode if kind == "decode" else _compile_prefill
    compiled, _params, _st = compile_(one_chip, bucket, int8)
    read = int(compiled.cost_analysis()["bytes accessed"])
    before, now = _BYTES_ACCESSED[(kind, int8, bucket)]
    logits = 4 * VOCAB * (SLOTS if kind == "decode" else 1)
    assert read == now and before < now <= before + 6 * logits


def test_decode_step_returns_the_picked_tokens_beside_the_logits(
        one_chip, for_the_chip):
    """One program a step still: its outputs are the slots' int32 argmax,
    the float32 logits it was taken over, and the cache aliased whole."""
    import jax
    compiled, _params, state = _compile_decode(one_chip, 512, False)
    picked, logits, new_state = compiled.out_info
    assert (picked.shape, str(picked.dtype)) == ((SLOTS,), "int32")
    assert (logits.shape, str(logits.dtype)) == ((SLOTS, VOCAB), "float32")
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(
        new_state)] == [(a.shape, a.dtype) for a in state]
    assert compiled.memory_analysis().alias_size_in_bytes == _nbytes(state)
    # the argmax is a reduce of the same program, not a second one
    assert len(re.findall(r"^ENTRY", compiled.as_text(), re.M)) == 1


def _mla_moe_arch(max_seq):
    """Two layers of the second family at the published widths: the
    leading dense layer and one sparse layer, 16 of 128 experts held."""
    return {"model_type": "sarvam_mla", "hidden_size": 4096,
            "num_attention_heads": 64, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 16384,
            "moe_intermediate_size": 2048, "num_shared_experts": 1,
            "num_experts": 128, "experts_held": [0, 16],
            "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
            "rms_norm_eps": 1e-6, "num_hidden_layers": 2,
            "first_k_dense_replace": 1, "rope_theta": 10000,
            "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                             "original_max_position_embeddings": 4096,
                             "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                             "mscale_all_dim": 1},
            "max_position_embeddings": max_seq, "vocab_size": 32768,
            "dtype": "bfloat16"}


def _mla_moe_family(one_chip, slots, max_seq):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import mla_moe as layer
    from mxnet_tpu.serve.mla_moe import MlaMoeDecoder

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    arch = layer.Arch(_mla_moe_arch(max_seq))
    params = {n: sd(*shape) for n, shape in layer.param_shapes(arch).items()}
    family = MlaMoeDecoder.__new__(MlaMoeDecoder)
    family.arch = family.cfg = arch
    state = tuple(sd(*p.shape(slots, max_seq))
                  for p in family.planes(max_seq, 128, False))
    return family, params, state, sd


def test_latent_cache_is_appended_in_place_at_published_widths(one_chip,
                                                               for_the_chip):
    """The second family's decode step over 48 slots of 4096 at bucket
    4096: the plane aliased whole, next to no temporaries, the grouped
    products Mosaic kernels, and a step reads the weights and the bucket's
    rows (for the scores and again for the mix), never a copy of the
    plane."""
    import jax.numpy as jnp
    slots, max_seq, s_b = 48, 4096, 4096
    family, params, state, sd = _mla_moe_family(one_chip, slots, max_seq)
    assert state[0].shape == (2, slots, max_seq, 640)
    compiled = family.build_decode(s_b).lower(
        params, state, sd(slots, dtype=jnp.int32),
        sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()
    # the slots' tokens, then the step's assignments and experts hit
    picked, logits, _state_out = compiled.out_info
    assert (picked.shape, str(picked.dtype)) == ((slots + 2,), "int32")
    assert (logits.shape, str(logits.dtype)) == ((slots, 32768), "float32")
    # the cost model reads 6 planes beside the weights (scores and mix
    # read the bucket's rows, here all of them, and an in-place append is
    # charged its operand); the positions-minor layout cost ten times the
    # plane a layer, twenty here
    read = compiled.cost_analysis()["bytes accessed"]
    allowed = _nbytes(params) + 8 * _nbytes(state)
    assert read < allowed, (read, allowed)


def test_a_prefill_chunk_appends_in_place_and_fits_beside_the_weights(
        one_chip, for_the_chip):
    """A chunk of 1024 over a context of 2048, per head over keys and
    values expanded from the latent: the plane aliased whole, and the
    temporaries (a block of queries' scores) under a gigabyte, which is
    what the 16 GB chip has left beside 9.5 GB of weights and the cache."""
    import jax.numpy as jnp
    family, params, state, sd = _mla_moe_family(one_chip, 48, 4096)
    compiled = family.build_prefill((1024, 2048)).lower(
        params, state, sd(1024, dtype=jnp.int32), sd(dtype=jnp.int32),
        sd(dtype=jnp.int32), sd(dtype=jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 1e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernels_compile_at_the_training_cells_widths(one_chip,
                                                            causal):
    """ISSUE 31: the forward and the one-pass backward at ``opt13_fit``'s
    shapes (64 heads of 2 rows, 2048 positions, d_head 64, bf16) through
    Mosaic. The row statistics cross HBM as lane-dense rows (the old
    ``f32[64,2048,8]`` was laid out 128 wide: 67 MB for half a megabyte),
    and the walk steps over no tile it does no arithmetic on (the old grid
    read 30 visited of 48 a head under ``causal``)."""
    import importlib
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
    head = jax.ShapeDtypeStruct((64, 2048, D_HEAD), jnp.bfloat16,
                                sharding=one_chip)
    block_q, block_k = fa._blocks(2048, 2048, 512, 512)

    def grads(q, k, v):
        return jax.grad(
            lambda *a: fa._fa(*a, D_HEAD ** -0.5, causal, block_q, block_k,
                              False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    fa._fa_forward.clear_cache()    # built, and counted, in here
    fa._fa_backward.clear_cache()
    with mx.profiler.counter_delta() as tiles:
        compiled = jax.jit(grads).trace(head, head, head).lower(
            lowering_platforms=("tpu",)).compile()
    visited = tiles.get("flash_attn_tiles_visited")
    assert visited and visited == tiles.get("flash_attn_tiles_grid")
    whole = 2 * (2048 // block_q) * (2048 // block_k)  # two kernels
    assert visited == whole if not causal else visited < 0.7 * whole
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, calls
    for line in calls:
        assert not re.search(r"f32\[[\d,]*,8\]", line), line
        # lse and delta: a row a Q block
        assert "f32[64,%d,%d]" % (2048 // block_q, block_q) in line, line


# ------------------------------------------------- ISSUE 33: the third family


def _sparse_linear_family(one_chip, slots, max_seq):
    """Two layers of the third family at the published widths, one of each
    kind: 32 query heads for 2 key/value heads of 128, 32 lightning heads,
    the whole vocabulary, bfloat16."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import sparse_linear as layer
    from mxnet_tpu.serve.sparse_linear import SparseLinearDecoder

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    arch = layer.Arch({
        "model_type": "minicpm_sala", "hidden_size": 4096,
        "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
        "intermediate_size": 16384, "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_head_dim": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 256,
        "mixer_types": ["minicpm4", "lightning-attn"],
        "depth_scale_layers": 32, "max_position_embeddings": max_seq,
        "vocab_size": 73448, "dtype": "bfloat16"})
    params = {n: sd(*shape) for n, shape in layer.param_shapes(arch).items()}
    family = SparseLinearDecoder.__new__(SparseLinearDecoder)
    family.arch = family.cfg = arch
    family.chunk = 1024
    state = tuple(sd(*p.shape(slots, max_seq), dtype=jnp.dtype(p.dtype))
                  for p in family.planes(max_seq, 128, False))
    return family, params, state, sd


def _kv_block_bytes(text):
    """Bytes of K and V a compiled decode program's kernel calls fetch a
    grid step: the block shapes of the cache operands, from the Mosaic
    call's own operand list."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return len(calls), sorted(set(re.findall(r"bf16\[[\d,]+\]", " ".join(
        calls))))


def test_the_third_familys_decode_reads_the_same_kv_at_two_buckets(
        one_chip, for_the_chip):
    """24 slots of 36 864 positions: the decode programs of buckets 16 384
    and 36 864 alias all four planes, hold next to no temporaries, read the
    selected blocks through the Mosaic kernel, and differ in the bytes they
    access by the compressed keys' rows alone (the indexer scores the
    bucket's ``kc`` rows; K and V are 64 blocks a slot whatever the
    bucket)."""
    import jax.numpy as jnp
    slots, max_seq = 24, 36864
    family, params, state, sd = _sparse_linear_family(one_chip, slots,
                                                      max_seq)
    assert [s.shape for s in state] == [
        (1, slots, max_seq, 256), (1, slots, max_seq, 256),
        (1, slots, max_seq // 16, 256), (1, slots, 4096, 128)]
    assert family.kernel_reads()
    read, texts = {}, {}
    for s_b in (16384, 36864):
        compiled = family.build_decode(s_b).lower(
            params, state, sd(slots, dtype=jnp.int32),
            sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.bool_)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == _nbytes(state)
        assert mem.temp_size_in_bytes < 50e6, mem.temp_size_in_bytes
        picked, logits, _state_out = compiled.out_info
        # the tokens, then the sparse layer's 64 block numbers a slot and
        # key/value head and a mean a head of what it read
        assert (picked.shape, str(picked.dtype)) == (
            (slots * (1 + 2 * 64 + 32),), "int32")
        assert (logits.shape, str(logits.dtype)) == ((slots, 73448),
                                                     "float32")
        read[s_b] = compiled.cost_analysis()["bytes accessed"]
        texts[s_b] = compiled.as_text()
        assert "tpu_custom_call" in texts[s_b]
        # the K and V planes reach the kernel whole: no slice of a bucket,
        # no copy of a plane
        assert not re.search(r"bf16\[\d+,%d,256\]\S* (copy|slice)\(" % s_b,
                             texts[s_b])
    kc_rows = slots * (36864 - 16384) // 16 * 256 * 2
    assert 0 <= read[36864] - read[16384] <= 1.05 * kc_rows, read
    assert _kv_block_bytes(texts[16384]) == _kv_block_bytes(texts[36864])
    # the weights once, the state read and written, a charge for each
    # in-place append's operand: nowhere near a second pass over a plane
    assert read[36864] < _nbytes(params) + 3 * _nbytes(state), read


def test_the_third_familys_prefill_chunk_fits_beside_the_weights(
        one_chip, for_the_chip):
    """A chunk of 1024 over the longest context, the selection as a
    block-level mask in blocks of queries: all four planes aliased, the
    temporaries under half a gigabyte."""
    import jax.numpy as jnp
    family, params, state, sd = _sparse_linear_family(one_chip, 24, 36864)
    compiled = family.build_prefill((1024, 36864)).lower(
        params, state, sd(1024, dtype=jnp.int32), sd(dtype=jnp.int32),
        sd(dtype=jnp.int32), sd(dtype=jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


def _window_moe_family(one_chip, slots=36, max_seq=36864):
    """The fourth family at the cell's own shapes: the configuration's
    seven layers at published widths, bfloat16, 36 slots of 36864."""
    import json
    import os
    import jax
    import jax.numpy as jnp
    from benchmarks.builders import mimo_window_moe as builder
    from mxnet_tpu.serve.window_moe import WindowMoeDecoder

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mimo-v2-flash-l7-ep16.json")) as f:
        cfg = json.load(f)
    params = {n: sd(*shape)
              for n, (shape, _m, _s) in builder.leaf_specs(cfg).items()}
    family = WindowMoeDecoder(params, builder.architecture(cfg))
    state = tuple(sd(*p.shape(slots, max_seq), dtype=jnp.dtype(p.dtype))
                  for p in family.planes(max_seq, 128, False))
    return family, params, state, sd


def test_the_fourth_familys_decode_reads_the_full_caches_with_the_kernel(
        one_chip, for_the_chip):
    """The decode step at the one bucket the cell's window runs at: the
    full layers' planes and the rings aliased whole, the grouped-query
    kernel called, next to no temporaries (no plane is laid out again),
    and behind the slots' tokens the step's assignments and experts
    hit."""
    import jax.numpy as jnp
    slots, s_b = 36, 36864
    family, params, state, sd = _window_moe_family(one_chip)
    assert [s.shape for s in state] == [
        (2, slots, s_b, 768), (2, slots, s_b, 512), (5, slots, 128, 1536),
        (5, slots, 128, 1024)]
    assert family.kernel_reads(s_b)
    compiled = family.build_decode(s_b).lower(
        params, state, sd(slots, dtype=jnp.int32),
        sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    assert "gqa_decode_attention" in compiled.as_text()
    picked, logits, _state_out = compiled.out_info
    assert (picked.shape, str(picked.dtype)) == ((slots + 2,), "int32")
    assert (logits.shape, str(logits.dtype)) == ((slots, 19072), "float32")


def test_the_engines_step_takes_the_last_tokens_and_moves_no_plane(
        one_chip, for_the_chip):
    """The program the engine dispatches, the fourth family's decode step
    behind the merge of the host's tokens with the previous step's: at the
    cell's shapes the planes are still aliased whole beside next to no
    temporaries, and the slots' tokens come out apart for the next step."""
    import jax.numpy as jnp
    from mxnet_tpu.serve.decode import DecodeEngine
    slots, s_b = 36, 36864
    family, params, state, sd = _window_moe_family(one_chip)
    engine = types.SimpleNamespace(
        family=family, cache=types.SimpleNamespace(max_slots=slots))
    compiled = DecodeEngine._build_step(engine, s_b).lower(
        params, state, sd(slots, dtype=jnp.int32),
        sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.int32),
        sd(slots, dtype=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    picked, _logits, _state_out, tokens = compiled.out_info
    assert (picked.shape, str(picked.dtype)) == ((slots + 2,), "int32")
    assert (tokens.shape, str(tokens.dtype)) == ((slots,), "int32")


@pytest.mark.parametrize("c_b,ctx_b", [(128, 4096), (1024, 36864)])
def test_the_fourth_familys_prefill_chunk_fits_beside_the_cache(
        one_chip, for_the_chip, c_b, ctx_b):
    """A chunk over a context: every plane aliased, and the temporaries
    under half a gigabyte beside 13.8 GB of weights and cache. A full
    layer's keys split into heads of 192 lanes had the compiler lay the
    whole 3.8 GB K plane out again (16.76 GB of 15.75)."""
    import jax.numpy as jnp
    family, params, state, sd = _window_moe_family(one_chip)
    compiled = family.build_prefill((c_b, ctx_b)).lower(
        params, state, sd(c_b, dtype=jnp.int32), sd(dtype=jnp.int32),
        sd(dtype=jnp.int32), sd(dtype=jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state)
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
