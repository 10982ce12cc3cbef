"""The main path's kernels and programs compile for a real v5e.

The sandbox has no chip, but the TPU compiler is installed and compiles
for a chip that is *described*, not attached. Interpret mode cannot see
what these catch: BlockSpec tiling violations, VMEM overflows, programs
that do not fit 16 GB of HBM, and Mosaic kernels called bare under a
multi-device jit (never auto-partitioned — the dp-sharded cases fail
with ``NotImplementedError`` without the ``shard_map`` wrapper). Nothing
runs: a compile that passes is not a chip run (``chip_smoke.py`` is).

This is the ONLY file that describes the chip. The topology, and every
sharding and mesh built from it, is made inside module-scoped fixtures:
only one process may load libtpu, so nothing here may touch it at import
or collection time, and every compile happens in this test's process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from rafiki_tpu.models import JaxTransformerLM
from rafiki_tpu.models.lm import _jitted_param_init
from rafiki_tpu.models.lm_generate import _build_decode, _build_prefill
from rafiki_tpu.ops import batch_sharded_flash_attention, flash_attention
from rafiki_tpu.parallel import build_mesh, replicated

HBM_BYTES = 16e9  # one v5e chip

#: The repo's flagship LM width (chip_smoke.py's FLAGSHIP), depth 8.
FLAGSHIP = {"d_model": 2048, "n_layers": 8, "seq_len": 2048,
            "vocab_size": 32768}


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off here.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dp_mesh(topo):
    return build_mesh(topo.devices)  # (dp=4, pp, ep, sp, tp = 1)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Mosaic kernel is not in the compiled program"
    return compiled


def _sum_grad(fn):
    """d(sum of fn)/d(q, k, v) — the backward kernels."""
    return jax.grad(
        lambda q, k, v, *rest: fn(q, k, v, *rest).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))


# (q's shape, dtype, flash_attention kwargs, with kv_mask); k and v have
# q's shape but for v's lanes in _V_LANES
_KERNEL_SHAPES = {
    # flagship LM step: B4·H16·T2048·D128 bf16 causal
    "flagship": ((4, 16, 2048, 128), jnp.bfloat16, {"causal": True},
                 False),
    # a long sequence: B2·H8·T8192·D128
    "long_t8192": ((2, 8, 8192, 128), jnp.bfloat16, {"causal": True},
                   False),
    # ViT-shaped: 197 tokens (not a block multiple), key-padding mask
    "masked_197x64": ((8, 4, 197, 64), jnp.bfloat16, {}, True),
    # Small explicit blocks with nq > 1: block_q is the backward
    # kernels' LANE dim and must round up to 128 (_flash_blocking) —
    # the regression the interpreter cannot catch.
    "small_blocks": ((1, 1, 256, 64), jnp.float32,
                     {"causal": True, "block_q": 32, "block_kv": 64},
                     False),
    # joyai-flash-final's latent attention: q, k 192 lanes (two MXU
    # passes), v 128 (one); kv blocks of 512 as models/lm_moe.py asks
    "mla": ((1, 32, 8192, 192), jnp.bfloat16,
            {"causal": True, "block_kv": 512}, False),
    # lfm2-moe-final's grouped-query attention: 32 query heads of 64
    # lanes (padded to 128) read 8 key-value heads
    "gqa": ((1, 32, 8192, 64), jnp.bfloat16, {"causal": True}, False),
}
_V_LANES = {"mla": 128}
_KV_HEADS = {"gqa": 8}


def _qkv_shapes(shape_name, sharding):
    shape, dtype, _, _ = _KERNEL_SHAPES[shape_name]
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    kv = (shape[0], _KV_HEADS.get(shape_name, shape[1]), shape[2])
    k = jax.ShapeDtypeStruct(kv + shape[3:], dtype, sharding=sharding)
    v = jax.ShapeDtypeStruct(
        kv + (_V_LANES.get(shape_name, shape[3]),), dtype,
        sharding=sharding)
    return [x, k, v]


@pytest.mark.parametrize("case", [
    "flagship-fwd", "flagship-grad",
    "long_t8192-fwd",
    "masked_197x64-fwd", "masked_197x64-grad",
    "small_blocks-fwd", "small_blocks-grad",
    "mla-fwd", "mla-grad", "gqa-fwd", "gqa-grad"])
def test_flash_kernel_compiles_on_one_chip(one_chip, case):
    shape_name, pass_ = case.split("-")
    grad = pass_ == "grad"
    shape, _, kwargs, masked = _KERNEL_SHAPES[shape_name]
    args = _qkv_shapes(shape_name, one_chip)
    if masked:
        args.append(jax.ShapeDtypeStruct(
            (shape[0], shape[2]), jnp.bool_, sharding=one_chip))

    def attend(q, k, v, mask=None):
        return flash_attention(q, k, v, kv_mask=mask, interpret=False,
                               **kwargs)

    _compile(_sum_grad(attend) if grad else attend, *args)


def test_flash_kernels_carry_their_names_into_the_compiled_program(
        one_chip):
    """``pallas_call(metadata={"kernel": ...})`` is what survives into
    the HLO text a profiler's device op event carries (a
    ``jax.named_scope`` does not): each of the three kernels of a
    training step is told from the others by name."""
    import re

    shape, dtype, kwargs, _ = _KERNEL_SHAPES["flagship"]
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, **kwargs)

    text = _compile(_sum_grad(attend), x, x, x).as_text()
    named = re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', text)
    assert set(named) == {"flash_fwd", "flash_dq", "flash_dkv"}


def _cell_knobs(config_name):
    """The knobs a benchmark configuration pins, as the driver's
    template gets them."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    knobs = {knob: config[key] for knob, key in config["knob_of"].items()}
    knobs.update(config["knobs"])
    return knobs


def _joyai_knobs():
    """The knobs of the benchmark's ``joyai-flash-final`` cell."""
    return _cell_knobs("joyai-llm-flash-L5-E8")


def _lfm2_knobs():
    """The knobs of the benchmark's ``lfm2-moe-final`` cell."""
    return _cell_knobs("lfm2-8b-a1b-L5-E8")


def _kernels_the_benchmark_reads(text, reader_name="mla_attn_fwd_roofline",
                                 knobs=_joyai_knobs):
    """``benchmarks/metrics/<reader_name>.py:kernels`` (the MLA reader,
    or the grouped-query one with the ``lfm2-moe-final`` knobs) over the
    Mosaic calls of a compiled program: {kernel: calls found}, once with
    the kernels' names in the text and once by signature alone, and the
    results' shapes of each call. The reader takes a profiler's op
    events, whose text states every operand's shape; the compiled text
    names operands only, so each gets its defining instruction's."""
    import os
    import re
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import harness
        reader = harness.load_module("metrics", reader_name)
    finally:
        sys.path.remove(bench)
    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\]\S*) ", text, re.M))
    named, bare, results = {}, {}, {}
    for head, operands, attrs in re.findall(
            r"^\s*(?:ROOT )?(%[^\n]*? custom-call\()([^)\n]*)(\), "
            r'custom_call_target="tpu_custom_call".*?\n\}\})', text,
            re.M | re.S):
        operands = ", ".join(
            f"{shape_of[name]} {name}" for name in re.findall(
                r"%[\w.\-]+", operands))
        op = {"n": 1, "seconds": 1.0}
        named[head + operands + attrs] = op
        bare[head + operands + attrs.partition(", operand_layout")[0]] = op
        results[re.search(r'"kernel":"(\w+)"', attrs).group(1)] = \
            re.findall(r"\w+\[[\d,]*\]", head.partition(" custom-call")[0])
    counts = []
    for ops in (named, bare):
        found = reader.kernels({"trace": {"ops": ops}, "knobs": knobs()})
        counts.append({name: k["n"] for name, k in found.items()})
    assert counts[0] == counts[1], counts
    return counts[0], results


def test_benchmark_readers_find_the_mla_kernels_in_the_compiled_program(
        one_chip):
    """The benchmark's roofline readers, which no ``perf_opt`` PR may
    edit, count a Mosaic call only if its first three operands are all
    (batch x heads, T, q's lanes padded): (q, k, v) in the forward,
    (k, v, q) in dq and dkv. So v's array keeps q's 256 lanes while its
    blocks, o, do and dv are 128 wide. A change of operand order or
    shape fails here and not in a chip check."""
    _, _, kwargs, _ = _KERNEL_SHAPES["mla"]

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, **kwargs)

    text = _compile(_sum_grad(attend),
                    *_qkv_shapes("mla", one_chip)).as_text()
    counts, results = _kernels_the_benchmark_reads(text)
    assert counts == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}
    assert results == {
        "flash_fwd": ["bf16[32,8192,128]", "f32[32,8192,8]"],
        "flash_dq": ["bf16[32,8192,256]"],
        "flash_dkv": ["bf16[32,8192,256]", "bf16[32,8192,128]"]}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_kernel_compiles_batch_sharded_over_dp4(dp_mesh, grad):
    """The four-chip path: batch over a 4-device dp mesh. Bare, the
    lowering refuses (Mosaic kernels cannot be partitioned); inside
    the shard_map wrapper it compiles with one kernel per device."""
    shape, dtype, kwargs, _ = _KERNEL_SHAPES["flagship"]
    x = jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(dp_mesh, P("dp")))

    def attend(q, k, v):
        return batch_sharded_flash_attention(q, k, v, dp_mesh,
                                             interpret=False, **kwargs)

    _compile(_sum_grad(attend) if grad else attend, x, x, x)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(lambda q, k, v: flash_attention(
            q, k, v, interpret=False, **kwargs), x, x, x)


@pytest.fixture(scope="module")
def flagship_lm(topo):
    """(model on a one-chip mesh of the described device, abstract
    params placed there). The model resolves its mesh from
    ``jax.devices()`` — the CPU here — so the test hands it the
    described chip instead."""
    model = JaxTransformerLM(**FLAGSHIP)
    mesh = build_mesh(topo.devices[:1])
    model._mesh = mesh
    s = model._dims()
    init = _jitted_param_init(s["v"], s["d"], s["layers"], mesh)
    params = jax.eval_shape(init, jax.ShapeDtypeStruct((), jnp.int32))
    rep = replicated(mesh)
    return model, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        params)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_generate_programs_compile_and_fit_one_chip(flagship_lm,
                                                    monkeypatch, program):
    """The generative engine's decode step and one prefill bucket at
    flagship width, lowered from shapes: weights (1.9 GB f32) + both
    K/V pools are arguments of the program, so its footprint is what
    has to fit the chip's HBM."""
    model, params = flagship_lm
    rep = replicated(model.mesh)
    s = model._dims()
    page_size, n_pages, batch, max_new = 16, 640, 8, 128
    pages_per_seq = -(-(s["t"] + max_new) // page_size)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    pool = sds((s["layers"], n_pages * page_size, s["d"]), jnp.bfloat16)
    if program == "decode":
        fn = _build_decode(s, page_size, pages_per_seq, batch)
        compiled = fn.lower(
            params, pool, pool, sds((batch,), jnp.int32),
            sds((batch, pages_per_seq), jnp.int32),
            sds((batch,), jnp.int32), sds((batch,), jnp.float32),
            sds((batch,), jnp.int32)).compile()
    else:
        # The model picks interpret mode from the host's backend (the
        # CPU here); steer the trace to the real kernel.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        bucket = 256
        fn = _build_prefill(s, bucket, model._block)
        compiled = fn.lower(
            params, pool, pool, sds((1, bucket), jnp.int32),
            sds((bucket,), jnp.int32), sds((), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_lm_evaluation_program_returns_a_scalar_and_fits_one_chip(
        flagship_lm, monkeypatch):
    """The evaluation's one program (``_eval_count_step``) at flagship
    width over four windows: the flash kernel is in it, and what it
    hands back is the count, four bytes, where the logits it reduces
    are 4 x 2048 x 32768 float32 = 1.07 GB of temporaries."""
    model, params = flagship_lm
    rep = replicated(model.mesh)
    s = model._dims()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    windows = jax.ShapeDtypeStruct((4, s["t"]), jnp.int32, sharding=rep)
    compiled = model._eval_count_step(4).lower(
        params, windows, windows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == () and out.dtype == jnp.int32
    # (the device pads the scalar to one 512-byte tile)
    assert compiled.memory_analysis().output_size_in_bytes <= 512
    assert _hbm_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_joyai_cell_step_compiles_and_fits_one_chip(topo, monkeypatch):
    """The train program of the benchmark's ``joyai-flash-final`` cell
    (JaxLatentMoELM at JoyAI-LLM-Flash's widths, 8 of 256 experts, 5 + 1
    blocks, 1 x 8192 tokens, 8 steps a dispatch, remat dots), lowered
    from shapes through the trainer's own ``_make_train_chunk``: the
    three flash kernels are in it by name (the dkv kernel at 256 padded
    lanes needs the halved kv block: 1024 x 1024 overflows the scoped
    VMEM) and as the benchmark's readers know them, in each of the
    dense, sparse and multi-token blocks (the forward once more under
    remat), and arguments + temporaries stay under the 14.5 GB that
    leave a job room for the previous trial's parameters."""
    import re

    import optax

    from rafiki_tpu.models import JaxLatentMoELM
    from rafiki_tpu.models.lm import _weights
    from rafiki_tpu.models.lm_moe import _jitted_moe_init

    model = JaxLatentMoELM(**_joyai_knobs())
    mesh = build_mesh(topo.devices[:1])
    model._mesh = mesh
    rep = replicated(mesh)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    s = model._dims()
    params = on_chip(jax.eval_shape(
        _jitted_moe_init(tuple(sorted(s.items())), mesh),
        jax.ShapeDtypeStruct((), jnp.int32)))
    assert sum(a.size for a in jax.tree.leaves(params)) > 490e6
    tx = optax.adamw(2.2e-4)
    opt_state = on_chip(jax.eval_shape(tx.init, _weights(params)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wins = jax.ShapeDtypeStruct((8, 1, s["t"] + 1), jnp.int32,
                                sharding=rep)
    compiled = model._make_train_chunk(tx).lower(
        params, opt_state, wins).compile()
    text = compiled.as_text()
    named = re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', text)
    assert set(named) == {"flash_fwd", "flash_dq", "flash_dkv"}
    counts, results = _kernels_the_benchmark_reads(text)
    assert counts == {"flash_fwd": 6, "flash_dq": 3, "flash_dkv": 3}
    assert results["flash_fwd"][0] == "bf16[32,8192,128]"  # v's lanes
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 5.8e9  # params + Adam, resident
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.5e9, m


def test_lfm2_cell_step_compiles_and_fits_one_chip(topo, monkeypatch):
    """The train program of the benchmark's ``lfm2-moe-final`` cell
    (JaxLfm2MoeLM at LFM2-8B-A1B's widths: conv, attention, conv, conv,
    conv with one leading dense layer, 8 of 32 experts, 1 x 8192
    tokens, 8 steps a dispatch, remat dots), lowered from shapes through
    the trainer's own ``_make_train_chunk``: the three flash kernels
    are in it by name, at 32 query heads over 8 key-value heads (the
    forward once more under remat) as the benchmark's grouped-query
    readers know them; the MLA reader, which knows a call by one shape
    three times, finds none of them; and arguments + temporaries + the
    previous trial's float32 tree, which a job keeps alive into the
    next trial's first steps, stay under 14.5 GB."""
    import re

    import optax

    from rafiki_tpu.models import JaxLfm2MoeLM
    from rafiki_tpu.models.lm import _weights
    from rafiki_tpu.models.lm_lfm2 import _jitted_lfm2_init

    model = JaxLfm2MoeLM(**_lfm2_knobs())
    mesh = build_mesh(topo.devices[:1])
    model._mesh = mesh
    rep = replicated(mesh)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), tree)

    s = model._dims()
    params = on_chip(jax.eval_shape(
        _jitted_lfm2_init(tuple(sorted(s.items())), mesh),
        jax.ShapeDtypeStruct((), jnp.int32)))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    assert 507e6 < n_params < 509e6
    tx = optax.adamw(2.2e-4)
    opt_state = on_chip(jax.eval_shape(tx.init, _weights(params)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wins = jax.ShapeDtypeStruct((8, 1, s["t"] + 1), jnp.int32,
                                sharding=rep)
    compiled = model._make_train_chunk(tx).lower(
        params, opt_state, wins).compile()
    text = compiled.as_text()
    named = re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"', text)
    assert set(named) == {"flash_fwd", "flash_dq", "flash_dkv"}
    assert _kernels_the_benchmark_reads(
        text, "lfm2_attn_fwd_roofline", _lfm2_knobs)[0] == {
            "flash_fwd": 2, "flash_dq": 1, "flash_dkv": 1}
    assert _kernels_the_benchmark_reads(text)[0] == {
        "flash_fwd": 0, "flash_dq": 0, "flash_dkv": 0}
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 6.0e9  # params + Adam, resident
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + 4 * n_params) < 14.5e9, m
