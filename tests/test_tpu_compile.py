"""Compile every Pallas kernel for a described TPU v5e chip.

Interpret mode never applies Mosaic's tiling rules, so a kernel can pass
every CPU test and still be refused by the TPU compiler.  These tests
lower and compile each kernel at real model widths against a
``v5e:2x2`` topology description (no chip attached; the TPU compiler is
part of the installed ``libtpu``) and check that the compiled HLO holds
the Mosaic custom call.  Nothing here runs a kernel.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every
pytest-xdist worker imports this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import lm

# (heads, kv_heads, head_dim) at published widths
ATTN = {"smollm-360m": (15, 5, 64), "qwen3-4b": (32, 8, 128)}
SLOTS, MAX_SEQ, PAGE = 8, 2048, 16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 2), ("data", "model"), devices=topo.devices)


@pytest.fixture
def kernel_dispatch(monkeypatch):
    """Model code takes the Pallas kernels, as it does on a chip (the
    backend of this process is the CPU)."""
    monkeypatch.setenv("REPRO_PALLAS", "kernel")


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _op_names(hlo: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo)


def _in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the name stack ``op_name``; a
    transformation wraps it, as in ``transpose(jvp(loss))``."""
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


def _compiled_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_flash_attention_compiles(arch, one_chip, no_compile_cache):
    H, K, D = ATTN[arch]
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention(q, k, v, causal=True), one_chip,
        ((1, MAX_SEQ, H, D), jnp.bfloat16), ((1, MAX_SEQ, K, D), jnp.bfloat16),
        ((1, MAX_SEQ, K, D), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_decode_attention_compiles(arch, one_chip, no_compile_cache):
    H, K, D = ATTN[arch]
    hlo = _compiled_hlo(
        decode_attention, one_chip,
        ((SLOTS, H, D), jnp.bfloat16), ((K, SLOTS, MAX_SEQ, D), jnp.bfloat16),
        ((K, SLOTS, MAX_SEQ, D), jnp.bfloat16), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(ATTN))
def test_decode_attention_paged_compiles(arch, one_chip, no_compile_cache):
    H, K, D = ATTN[arch]
    W = MAX_SEQ // PAGE
    pool = ((K, SLOTS * W, PAGE, D), jnp.bfloat16)
    hlo = _compiled_hlo(
        decode_attention_paged, one_chip,
        ((SLOTS, H, D), jnp.bfloat16), pool, pool, ((SLOTS, W), jnp.int32),
        ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("with_h0", [False, True])
def test_ssd_scan_compiles_mamba2_130m(with_h0, one_chip, no_compile_cache):
    from repro.configs import get_config

    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    H, P, G, N = s.n_heads(cfg.d_model), s.head_dim, s.n_groups, s.d_state
    S = MAX_SEQ
    shapes = [((1, S, H, P), jnp.bfloat16), ((1, S, H), jnp.float32),
              ((H,), jnp.float32), ((1, S, G, N), jnp.bfloat16),
              ((1, S, G, N), jnp.bfloat16)]
    if with_h0:
        shapes.append(((1, H, P, N), jnp.float32))

    def fn(x, dt, A, Bm, Cm, h0=None):
        return ssd_scan(x, dt, A, Bm, Cm, chunk=s.chunk, h0=h0,
                        return_final_state=True)

    hlo = _compiled_hlo(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo


def test_sharded_train_step_compiles_smollm_360m(mesh_2x2, kernel_dispatch,
                                                 no_compile_cache):
    """The ZeRO-1 train step that ``chip_smoke.py --four-chips`` runs, at
    full width on the (data=2, model=2) mesh.  XLA cannot partition a
    Mosaic call, so this compiles only if every kernel runs per shard."""
    from repro.configs import ShapeConfig, get_config
    from repro.launch.inputs import input_specs
    from repro.sharding.rules import make_rules, use_rules
    from repro.train.optimizer import get_optimizer
    from repro.train.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = get_config("smollm-360m")
    rules = make_rules(mesh_2x2)
    opt = get_optimizer("adamw")
    step_fn = make_train_step(cfg, opt, constant(3e-4))
    args = input_specs(cfg, ShapeConfig("train", MAX_SEQ, 4, "train"), rules,
                       opt=opt, zero1=True)

    def step(*a):
        with use_rules(rules):
            return step_fn(*a)

    assert "tpu_custom_call" in jax.jit(step).lower(*args).compile().as_text()


@pytest.mark.parametrize("layout", ["dense", "stacked", "paged"])
def test_sharded_decode_keeps_the_cache_local(layout, mesh_2x2,
                                              kernel_dispatch,
                                              no_compile_cache):
    """qwen3-4b's 8 KV heads divide the model axis: each chip attends with
    its own heads of the cache, and no collective gathers the cache."""
    from repro.kernels import ops
    from repro.sharding.rules import make_rules, use_rules

    H, K, D = ATTN["qwen3-4b"]
    rules = make_rules(mesh_2x2)

    def sds(shape, dtype, logical):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=rules.sharding(logical, shape))

    q = sds((SLOTS, H, D), jnp.bfloat16, ("batch", "heads", None))
    kv_len = sds((SLOTS,), jnp.int32, ("batch",))
    if layout == "dense":       # the layouts of models/attention.py
        cache = sds((K, SLOTS, MAX_SEQ, D), jnp.bfloat16,
                    ("kv_heads", "batch", "seq_kv", None))
        fn, args = ops.decode_attention, (q, cache, cache, kv_len)
    elif layout == "stacked":   # every layer's cache, read at one layer
        cache = sds((4, K, SLOTS, MAX_SEQ, D), jnp.bfloat16,
                    (None, "kv_heads", "batch", "seq_kv", None))
        layer = sds((), jnp.int32, ())
        fn, args = ops.decode_attention, (q, cache, cache, kv_len, layer)
    else:
        W = MAX_SEQ // PAGE
        pool = sds((K, SLOTS * W, PAGE, D), jnp.bfloat16,
                   ("kv_heads", None, "seq_kv", None))
        table = sds((SLOTS, W), jnp.int32, ("batch", None))
        fn, args = ops.decode_attention_paged, (q, pool, pool, table, kv_len)

    def decode(*a):
        with use_rules(rules):
            return fn(*a)

    hlo = jax.jit(decode).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo


def _cache_sized_copies(hlo: str, shapes) -> list[str]:
    """Copies and dynamic-update-slice fusions whose result has one of
    ``shapes``, unit dims aside.  An unfused dynamic-update-slice updates
    its operand in place and is not counted."""
    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]",
                         hlo, re.M):
        name = m.group(1)
        dims = tuple(int(d) for d in m.group(2).split(",") if d not in
                     ("", "1"))
        if dims in shapes and (name.startswith("copy") or (
                "dynamic-update-slice" in name and "fusion" in name)):
            out.append(name)
    return out


def test_step_programs_write_the_kv_cache_in_place(one_chip,
                                                   kernel_dispatch,
                                                   no_compile_cache):
    """qwen3-4b's fused decode and chunked prefill programs, 4 layers
    deep with 8 slots x 2048, carry the stacked KV cache through their
    layer loops: no copy of the whole cache or of one layer's [K, B, S,
    hd] slice, in or around the loops.  The decode kernel reads the
    stacked cache and keeps the signature that
    ``chipbench/metrics/decode_attention_roofline.py`` finds it by: four
    operands and one [heads, head_dim] row per slot."""
    from repro.configs import get_config
    from repro.models.params import abstract_params
    from repro.serve.engine import _fused_steps, _prefill_chunk

    H, K, D = ATTN["qwen3-4b"]
    L, C = 4, 128
    cfg = get_config("qwen3-4b").replace(num_layers=L)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    params = on_chip(abstract_params(lm.make_lm(cfg)))
    cache = on_chip(abstract_params(lm.make_cache(cfg, SLOTS, MAX_SEQ)))
    shapes = {(L, K, SLOTS, MAX_SEQ, D), (K, SLOTS, MAX_SEQ, D)}
    state = {"tokens": arg((SLOTS, 1)), "pos": arg((SLOTS,)),
             "cursor": arg((SLOTS,)), "plen": arg((SLOTS,)),
             "remaining": arg((SLOTS,)), "live": arg((SLOTS,), bool),
             "keys": arg((SLOTS, 2), jnp.uint32)}
    fused = _fused_steps.lower(
        cfg, 8, params, cache, state, arg((SLOTS, MAX_SEQ)),
        arg((SLOTS,), jnp.float32), arg((SLOTS,)), None, None
    ).compile().as_text()
    assert _cache_sized_copies(fused, shapes) == []
    calls = [ln for ln in fused.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert calls
    for ln in calls:
        result = re.match(r"\s*%?[\w.\-]+ = \w+\[([\d,]*)\]", ln).group(1)
        operands = ln.partition("custom-call(")[2].partition(
            "), custom_call_target")[0]
        assert math.prod(int(d) for d in result.split(",")) == SLOTS * H * D
        assert operands.count("%") == 4, ln
    chunk = _prefill_chunk.lower(
        cfg, params, cache, arg((SLOTS, C)), arg((SLOTS,)),
        arg((SLOTS,), bool), None).compile().as_text()
    assert _cache_sized_copies(chunk, shapes) == []


def test_deepseek_v2_lite_share_fits_and_names_its_scopes(one_chip,
                                                         kernel_dispatch,
                                                         no_compile_cache):
    """The ``deepseek-v2-lite-ep8.reason`` cell's programs, all 27 layers
    with routed experts 0-7 held, 32 slots x 4096 of latent cache: the
    fused decode (8 steps) and the 128-token prefill chunk fit a v5e's
    16 GB with room, the experts run as XLA's ragged dot, and the scopes
    that the cell's per-layer metrics read are in the operations' names.

    Both programs carry the stacked latent cache in place: no copy and no
    dynamic-update-slice fusion of a layer's ``ckv`` [B, S, 512] or of the
    26 MoE layers' ``ckv`` and ``kpe`` stacks.  What remains is the dense
    first layer's own ``kpe`` [1, B, S, 64] in the fused decode: laid out
    anew at the program's edges, once a call (two copies), and moved into
    and out of fast memory (two copy-dones).  Its count is pinned here so
    that it cannot grow; the prefill chunk has none."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.params import abstract_params
    from repro.serve.engine import _fused_steps, _prefill_chunk

    B, S, C = 32, 4096, 128
    cfg = get_config("deepseek-v2-lite")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held_count=8))
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    params = on_chip(abstract_params(lm.make_lm(cfg)))
    cache = on_chip(abstract_params(lm.make_cache(cfg, B, S)))
    state = {"tokens": arg((B, 1)), "pos": arg((B,)), "cursor": arg((B,)),
             "plen": arg((B,)), "remaining": arg((B,)),
             "live": arg((B,), bool), "keys": arg((B, 2), jnp.uint32)}
    fused = _fused_steps.lower(
        cfg, 8, params, cache, state, arg((B, S)), arg((B,), jnp.float32),
        arg((B,)), None, None).compile()
    chunk = _prefill_chunk.lower(
        cfg, params, cache, arg((B, C)), arg((B,)), arg((B,), bool),
        None).compile()
    m, moe_layers = cfg.mla, cfg.num_layers - 1
    stacks_and_slices = {(moe_layers, B, S, m.kv_lora_rank),
                         (B, S, m.kv_lora_rank),
                         (moe_layers, B, S, m.qk_rope_head_dim)}
    rope_slice = {(B, S, m.qk_rope_head_dim)}
    for program, edge_copies in ((fused, 4), (chunk, 0)):
        hlo = program.as_text()
        assert _cache_sized_copies(hlo, stacks_and_slices) == []
        assert len(_cache_sized_copies(hlo, rope_slice)) <= edge_copies
    for program, scopes in ((fused, ("mla_latent_attention", "moe_route",
                                     "moe_dispatch", "moe_experts",
                                     "moe_combine")),
                            (chunk, ("mla_latent_attention", "moe_experts"))):
        mem = program.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert peak < 0.9 * 16e9, peak
        hlo = program.as_text()
        assert "ragged-dot" in hlo
        op_names = _op_names(hlo)
        for scope in scopes:
            assert any(_in_scope(n, scope) and _in_scope(n, "mla" if
                       scope.startswith("mla") else "moe")
                       for n in op_names), scope


def test_train_step_names_the_attention_backward(one_chip, kernel_dispatch,
                                                 no_compile_cache):
    """The chip's train step runs the flash kernel forward and the XLA
    reference backward; the backward's operations carry ``attention_bwd``
    in their ``op_name`` metadata, so a device profile can attribute them,
    and the block scopes are there too.  The jitted program keeps its
    module name."""
    from repro.configs import reduced_config
    from repro.train.optimizer import AdamW
    from repro.train.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = reduced_config("smollm-360m")
    opt = AdamW()
    params = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    step = make_train_step(cfg, opt, constant(3e-4))
    hlo = jax.jit(step).lower(
        on_chip(params), on_chip(opt_state),
        {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32,
                                        sharding=one_chip)},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert hlo.startswith("HloModule jit_train_step")
    assert "tpu_custom_call" in hlo
    op_names = _op_names(hlo)
    bwd = [n for n in op_names if _in_scope(n, "attention_bwd")]
    assert bwd and all(_in_scope(n, "attention") for n in bwd)
    for scope in ("mlp", "loss", "head", "adamw"):
        assert any(_in_scope(n, scope) for n in op_names), scope


def test_serving_programs_keep_their_module_names():
    """Profile readers find the engine's programs by module name."""
    from repro.configs import reduced_config
    from repro.serve.engine import DecodeEngine, _fused_steps, _prefill_chunk

    cfg = reduced_config("smollm-360m")
    eng = DecodeEngine(cfg, lm.init_lm(cfg, jax.random.PRNGKey(0)),
                       batch_slots=2, max_seq=32, prefill_chunk=8)
    state = {k: jnp.asarray(getattr(eng, k)) for k in
             ("tokens", "pos", "cursor", "plen", "remaining", "live", "keys")}
    fused = _fused_steps.lower(
        cfg, 2, eng.params, eng.cache, state, jnp.asarray(eng.prompt_buf),
        jnp.asarray(eng.temp), jnp.asarray(eng.topk), None, None)
    chunk = _prefill_chunk.lower(
        cfg, eng.params, eng.cache, jnp.zeros((2, 8), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool), None)
    assert fused.as_text().startswith("module @jit__fused_steps")
    assert chunk.as_text().startswith("module @jit__prefill_chunk")
    op_names = _op_names(fused.compile().as_text())
    for scope in ("attention", "mlp", "head", "sampler"):
        assert any(_in_scope(n, scope) for n in op_names), scope
