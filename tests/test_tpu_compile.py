"""The main-path Pallas kernels compile for a TPU v5e at deployment widths.

Interpret mode cannot see what Mosaic refuses (a float iota, a slice that
breaks the tiling, more VMEM than a kernel may use), so every kernel the
ICOA main path launches is compiled here for a described v5e chip that is
not attached, at Dp=128 and Dp=512 (the widest the VMEM plan at
block_n=2048 holds) with Np=65536.  Nothing runs; only the TPU compiler
checks.

The topology is described inside a module fixture: only the worker that runs
these tests loads the TPU library, and every worker collects the same tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gram import kernel as gram_k
from repro.kernels.sweep import kernel as sweep_k

NP = 65536
BATCH = 8
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, F32, sharding=sharding) for s in shapes]


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases(dp: int):
    """(kernel, operand shapes) for every main-path kernel at width dp."""
    r, m, col, plate = (dp, NP), (dp, dp), (dp, 8), (8, 128)
    row = (8, NP)

    def b(*shapes):
        return tuple((BATCH,) + s for s in shapes)

    return {
        "gram": (gram_k.gram_pallas, (r,)),
        "gram_batched": (gram_k.gram_pallas_batched, b(r)),
        "row_gram": (gram_k.row_gram_pallas, (r, row)),
        "row_gram_batched": (gram_k.row_gram_pallas_batched, b(r, row)),
        "probe": (sweep_k.probe_sweep_pallas, (r, m, col, plate, plate)),
        "probe_batched": (sweep_k.probe_sweep_pallas_batched,
                          b(r, m, col, plate, plate)),
        "commit": (sweep_k.commit_sweep_pallas, (r, row, m, col, plate)),
        "commit_batched": (sweep_k.commit_sweep_pallas_batched,
                           b(r, row, m, col, plate)),
    }


_CASES = [(name, dp) for dp in (128, 512) for name in _kernel_cases(dp)]


@pytest.mark.parametrize("name,dp", _CASES,
                         ids=[f"{n}-dp{d}" for n, d in _CASES])
def test_kernel_compiles_for_v5e(one_chip, name, dp):
    kernel, shapes = _kernel_cases(dp)[name]

    def call(*args):
        return kernel(*args, interpret=False)

    text = _compiled_text(call, _shapes(one_chip, *shapes))
    assert "tpu_custom_call" in text


def test_fit_runner_carries_kernels_for_v5e(one_chip, monkeypatch):
    """The compiled fused-engine program of the on-chip smoke's fit spec
    (D=100 cosine parties, 64K instances) calls the Mosaic kernels."""
    from repro import api
    from repro.kernels.gram import ops as gram_ops
    from repro.kernels.sweep import ops as sweep_ops

    # this process's backend is the CPU, so the ops would pick the
    # interpreter; force the compiled kernels the TPU backend would pick
    for ops in (gram_ops, sweep_ops):
        monkeypatch.setattr(ops, "resolve_interpret", lambda explicit=None: False)
    spec = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_attrs=100, n_train=NP,
                          n_test=NP),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(name="icoa", engine="fused", use_kernel=True,
                              n_sweeps=10))
    trial = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compiled_text(api.build_runner(spec), (trial,))
    assert "tpu_custom_call" in text
