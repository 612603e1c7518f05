"""Compile the serving path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology
described on this host, so Mosaic's block-shape and memory checks run
at qwen3-4b's attention widths (Hq 32, Hkv 8, Dh 128, 16-token pages)
without a chip.  The topology is described inside a module fixture, never
at import time, and the tests skip from there where it cannot be.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

HQ, HKV, DH, PAGE = 32, 8, 128, 16
B, N_LOG, N_PAGES, L = 8, 128, 640, 36


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no chip compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _holds_kernel(compiled, name: str) -> bool:
    return any('custom_call_target="tpu_custom_call"' in line and name in line
               for line in compiled.as_text().splitlines())


def _arena_args(one_chip, dtype):
    arena = _sds(one_chip, (N_PAGES, L, HKV, PAGE, DH), dtype)
    scales = ({"k_scale": _sds(one_chip, (N_PAGES, L), jnp.float32),
               "v_scale": _sds(one_chip, (N_PAGES, L), jnp.float32)}
              if dtype == jnp.int8 else {})
    return arena, scales


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_kernel_compiles(one_chip, dtype):
    from repro.kernels.decode_attention.decode_attention import (
        paged_decode_attention_bhd,
    )
    arena, scales = _arena_args(one_chip, dtype)

    def f(q, k, v, bt, kv_len, layer, **sc):
        return paged_decode_attention_bhd(q, k, v, bt, kv_len, layer,
                                          interpret=False, **sc)
    compiled = jax.jit(f).lower(
        _sds(one_chip, (B, HQ, DH), jnp.bfloat16), arena, arena,
        _sds(one_chip, (B, N_LOG), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (), jnp.int32), **scales).compile()
    assert _holds_kernel(compiled, "paged_decode_attention")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_extend_kernel_compiles(one_chip, dtype):
    from repro.kernels.flash_attention.flash_attention import (
        paged_extend_attention_bhsd,
    )
    arena, scales = _arena_args(one_chip, dtype)
    S = 256

    def f(q, k, v, bt, pos, layer, **sc):
        return paged_extend_attention_bhsd(q, k, v, bt, pos, layer,
                                           interpret=False, **sc)
    compiled = jax.jit(f).lower(
        _sds(one_chip, (4, HQ, S, DH), jnp.bfloat16), arena, arena,
        _sds(one_chip, (4, N_LOG), jnp.int32), _sds(one_chip, (4,), jnp.int32),
        _sds(one_chip, (), jnp.int32), **scales).compile()
    assert _holds_kernel(compiled, "paged_extend_attention")


def test_paged_decode_step_compiles(topo, one_chip, monkeypatch):
    """The jitted paged decode step of a 2-layer model at qwen3-4b's
    attention widths: the kernel is in the program and the arena is
    updated in place (no arena-sized temporaries)."""
    import repro.kernels.decode_attention.ops as dops
    import repro.kernels.flash_attention.ops as fops
    import repro.models.layers as layers
    from repro.configs.base import with_opt_level
    from repro.configs.registry import get_arch
    from repro.models.cache_utils import page_arena, strip_kv_nodes
    from repro.models.model import build_model
    from repro.serve.kvpool import build_paged_serve_step
    from repro.sharding.rules import make_ctx

    # this host's backend is the CPU: steer the paged path to the kernels
    # and the kernels to the real Mosaic lowering
    monkeypatch.setattr(layers, "paged_kernels", lambda: True)
    monkeypatch.setattr(dops, "_on_tpu", lambda: True)
    monkeypatch.setattr(fops, "_on_tpu", lambda: True)

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = with_opt_level(get_arch("qwen3-4b"), True).replace(num_layers=2)
    model = build_model(cfg, make_ctx(mesh))
    max_len, n_pages = N_LOG * PAGE, 256

    def place(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        model.abstract_params(), model.ctx.params_shardings(model.param_specs()))
    arena = place(jax.eval_shape(lambda: page_arena(model, n_pages, PAGE)))
    resident = place(jax.eval_shape(
        lambda: strip_kv_nodes(model.init_cache(B, max_len))))
    step = jax.jit(build_paged_serve_step(model, 0.0,
                                          template=model.cache_specs(1, max_len)),
                   donate_argnums=(1, 2, 3))
    compiled = step.lower(
        params, arena, None, resident, _sds(one_chip, (B, N_LOG), jnp.int32),
        {"tokens": _sds(one_chip, (B, 1), jnp.int32),
         "pos": _sds(one_chip, (B,), jnp.int32)},
        place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    ).compile()
    assert _holds_kernel(compiled, "paged_decode_attention")
    arena_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(arena))
    assert compiled.memory_analysis().temp_size_in_bytes < arena_bytes // 8
