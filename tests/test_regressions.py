"""Regression pins for the seed-suite failure clusters.

Each test pins one of the API / correctness bugs fixed alongside the
disaggregated-serving PR so they cannot silently reappear:
  * the kernels' Pallas TPU compiler params (``pltpu.CompilerParams``)
  * ``cost_analysis()`` read as one dict into the cell's accounting
  * meshes built with Auto axes (``jax.make_mesh`` defaults to Explicit)
  * ``ArrayChannel.map`` silently allowing disjoint-device zero-copy
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


def test_tpu_compiler_params_resolves_on_this_jax():
    import importlib
    import inspect

    from jax.experimental.pallas import tpu as pltpu

    cp = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    assert cp.dimension_semantics == ("parallel", "arbitrary")
    for name in ("decode_attention.decode_attention",
                 "flash_attention.flash_attention", "moe_gmm.moe_gmm",
                 "ssd_scan.ssd_scan"):
        mod = importlib.import_module(f"repro.kernels.{name}")
        assert "pltpu.CompilerParams(" in inspect.getsource(mod)


def test_kernels_run_under_interpret_mode():
    """The kernels construct ``pltpu.CompilerParams`` directly; one
    representative call proves the pallas_call wiring still works."""
    from repro.kernels.flash_attention import flash_attention

    q = jnp.zeros((1, 8, 1, 8), jnp.float32)      # (B, S, H, Dh)
    out = flash_attention(q, q, q, block_q=8, block_k=8)
    assert out.shape == q.shape


def test_cost_analysis_list_and_dict_normalized():
    from repro.core.accounting import CellAccounting

    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    assert isinstance(compiled.cost_analysis(), dict)
    pc = CellAccounting("c").register_program("real", compiled)
    assert pc.flops_per_device > 0

    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 3.0}

        def memory_analysis(self):
            return None

        def as_text(self):
            return ""

    pc = CellAccounting("c").register_program("p", FakeCompiled())
    assert pc.flops_per_device == 7.0 and pc.bytes_per_device == 3.0


def test_cell_accounting_is_exact_after_training():
    """The old ``try/except: pass`` around register_program hid the crash
    and silently disabled exact accounting; now training must register the
    step program's real cost."""
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.configs.registry import get_arch
    from repro.core import DeviceGrid, Supervisor
    from repro.data.pipeline import DataConfig, SyntheticPipeline

    grid = DeviceGrid.from_flat(jax.devices()[:1], pods=1, rows=1, cols=1)
    sup = Supervisor(grid)
    arch = smoke_config(get_arch("qwen3-4b"))
    cell = sup.create_cell("t", arch, "train", ncols=1)
    pipe = SyntheticPipeline(DataConfig(kind="bigram", vocab=arch.vocab), arch,
                             ShapeConfig("t", "train", 2, 16))
    cell.train_steps(pipe.get_batch, 2)
    pc = cell.accounting.programs["train_step"]
    assert pc.flops_per_device > 0 and pc.invocations == 2
    assert cell.accounting.totals()["flops"] > 0


def test_mesh_helpers_work_without_axis_type():
    """mesh.py builds Auto-axis meshes: ``jax.make_mesh`` would otherwise
    default to Explicit axes, which the logical sharding rules do not
    use."""
    from repro.launch.mesh import make_mesh_for_devices

    mesh = make_mesh_for_devices(1, 1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_channel_map_requires_shared_devices():
    from repro.core.channels import ArrayChannel, ChannelError

    class FakeCell:
        def __init__(self, devices):
            self.mesh = type("M", (), {"devices": np.array(devices, dtype=object)})()

    d0, d1 = object(), object()
    shared = ArrayChannel(FakeCell([d0]), FakeCell([d0]))
    assert shared.map({"x": 1})["zero_copy"]
    assert shared.recv() == {"x": 1}

    disjoint = ArrayChannel(FakeCell([d0]), FakeCell([d1]))
    with pytest.raises(ChannelError):
        disjoint.map({"x": 1})


def test_collection_never_aborts_on_missing_hypothesis():
    """test_partition / test_train importorskip hypothesis instead of
    crashing collection (which killed the whole tier-1 -x run)."""
    import ast
    import os

    here = os.path.dirname(__file__)
    for mod in ("test_partition.py", "test_train.py"):
        src = open(os.path.join(here, mod)).read()
        tree = ast.parse(src)
        calls = [
            n for n in ast.walk(tree)
            if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "importorskip"
        ]
        assert calls, f"{mod} must importorskip hypothesis"
