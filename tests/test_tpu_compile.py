"""The main-path Pallas kernels compile for a TPU v5e at deployment widths.

Interpret mode cannot see what Mosaic refuses (a float iota, a slice that
breaks the tiling, more VMEM than a kernel may use), so every kernel the
ICOA main path launches is compiled here for a described v5e chip that is
not attached, with Np=65536, at Dp=8 (the sublane multiple every D<=8
pads to), Dp=104 (D=100), Dp=128 and Dp=512 (the widest the VMEM plan at
block_n=2048 holds).  Nothing runs; only the TPU compiler checks.

The topology is described inside a module fixture: only the worker that runs
these tests loads the TPU library, and every worker collects the same tests.
"""
from __future__ import annotations

import os
import re

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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """This process's backend is the CPU, so the ops would pick the
    interpreter; force the compiled kernels the TPU backend would pick."""
    from repro.kernels import runtime

    monkeypatch.setattr(runtime, "resolve_interpret",
                        lambda explicit=None: False)


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, F32, sharding=sharding) for s in shapes]


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases(dp: int):
    """(kernel, operand shapes) for every main-path kernel at width dp, at
    B=1 (an unbatched op call, as in the shard_map backend) and at B=8 (a
    vmapped trial batch)."""
    r, m, col, plate = (dp, NP), (dp, dp), (dp, 8), (8, 128)
    row = (8, NP)
    ops = {
        "gram": (gram_k.gram_pallas, (r,)),
        "row_gram": (gram_k.row_gram_pallas, (r, row)),
        "probe": (sweep_k.probe_sweep_pallas, (r, m, col, plate, plate)),
        "commit": (sweep_k.commit_sweep_pallas, (r, row, m, col, plate)),
    }
    return {f"{name}-b{b}": (kernel, tuple((b,) + s for s in shapes))
            for name, (kernel, shapes) in ops.items() for b in (1, BATCH)}


_CASES = [(name, dp) for dp in (8, 104, 128, 512)
          for name in _kernel_cases(dp)]


@pytest.mark.parametrize("name,dp", _CASES,
                         ids=[f"{n}-dp{d}" for n, d in _CASES])
def test_kernel_compiles_for_v5e(one_chip, name, dp):
    kernel, shapes = _kernel_cases(dp)[name]

    def call(*args):
        return kernel(*args, interpret=False)

    text = _compiled_text(call, _shapes(one_chip, *shapes))
    assert "tpu_custom_call" in text


def test_fit_runner_carries_kernels_for_v5e(one_chip, mosaic_kernels):
    """The compiled fused-engine program of the on-chip smoke's fit spec
    (D=100 cosine parties, 64K instances) calls the Mosaic kernels."""
    from repro import api

    spec = api.ExperimentSpec(
        data=api.DataSpec(source="cosine", n_attrs=100, n_train=NP,
                          n_test=NP),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 4),)),
        solver=api.SolverSpec(name="icoa", engine="fused", use_kernel=True,
                              n_sweeps=10))
    trial = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compiled_text(api.build_runner(spec), (trial,))
    assert "tpu_custom_call" in text


def _mesh_program_text(topo, alpha: float) -> str:
    """The shard_map backend's Monte-Carlo program for four parties of two
    columns on the four chips of a v5e:2x2, at 64K instances, with the fused
    engine's kernels, compiled for two trials."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro import api

    spec = api.ExperimentSpec(
        data=api.DataSpec(source="correlated_linear", n_attrs=8,
                          partition="blocks", n_agents=4, n_train=NP,
                          n_test=NP),
        agent=api.AgentSpec(family="polynomial", options=(("degree", 1),)),
        solver=api.SolverSpec(name="icoa", engine="fused", use_kernel=True,
                              n_sweeps=10, alpha=alpha),
        backend=api.BackendSpec(name="shard_map"))
    mesh = Mesh(np.array(topo.devices[:4]), ("agents",))
    run_fn = api.build_distributed_runner(spec, mesh=mesh)

    def loop(trials):
        return jax.lax.scan(lambda c, t: (c, run_fn(t)),
                            jnp.asarray(0, jnp.int32), trials)[1]

    trials = jax.ShapeDtypeStruct((2,), jnp.int32,
                                  sharding=NamedSharding(mesh, PartitionSpec()))
    return _compiled_text(loop, (trials,))


def _shard_map_indexing(text: str) -> list:
    """(result type, opcode) of each gather and scatter that the compiled
    program runs inside the shard_map, told by its op_name metadata."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= (\w+\[[\d,]*\])\S* (gather|scatter)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and "shard_map" in name.group(1):
            out.append(m.groups())
    return out


def test_mesh_batch_program_carries_kernels_for_v5e_2x2(topo, mosaic_kernels):
    """XLA cannot partition a Mosaic kernel, so every kernel must sit inside
    a shard_map (the record's Gram included).  At alpha 1 the sweep body
    moves the full-length residual rows as they are: no f32[N] gather or
    scatter by an identity index is left inside the shard_map."""
    text = _mesh_program_text(topo, alpha=1.0)
    assert "tpu_custom_call" in text and "all-gather" in text
    indexing = _shard_map_indexing(text)
    assert not [op for op in indexing if op[0] == f"f32[{NP}]"], indexing


def test_mesh_batch_program_keeps_the_subsample_for_v5e_2x2(topo,
                                                           mosaic_kernels):
    """Minimax Protection's rate alpha > 1 still gathers its random N/alpha
    subsample inside the shard_map and scatters the step back to f32[N]."""
    indexing = _shard_map_indexing(_mesh_program_text(topo, alpha=4.0))
    assert ("f32[16384]", "gather") in indexing, indexing
    assert (f"f32[{NP}]", "scatter") in indexing, indexing
