"""Compile the simulator's chip programs for a described TPU v5e.

Nothing runs here: each test lowers a kernel or a whole engine program
against a ``v5e:2x2`` topology that JAX describes without a chip attached,
and compiles it with the TPU compiler. That catches what interpret mode
cannot — Mosaic refusing an op (reverse-mode autodiff through a kernel),
misaligned tiles, and VMEM overruns — at no chip time. Every compile that
must succeed asserts the program contains the kernel (``tpu_custom_call``),
so an XLA stand-in cannot pass for it.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from helpers import gather_index_shapes
from jax.sharding import SingleDeviceSharding

from repro.core import classifier, engine
from repro.kernels.grid_tick import grid_tick_bank_fused_pallas
from repro.kernels.selu_mlp import selu_mlp_pallas
from repro.train.optimizer import AdamWConfig, adamw_init

F32, I32 = jnp.float32, jnp.int32

# (scenarios, replicas, legs, procs, links): the bench fleet's bucket pads
# and the paper's production workload at calibration batch width
BENCH = (64, 4, 58, 58, 11)
PRODUCTION = (1, 4096, 106, 11, 1)
# the access-profile search over the production campaign: its super-table of
# three candidates an access, 512 members as the replica rows
SEARCH = (1, 512, 424, 329, 3)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel compiled"


def _bank_spec(sds, s, t, p, l):
    return engine.SimSpec(
        size_mb=sds((s, t)), release=sds((s, t), I32), dep=sds((s, t), I32),
        profile=sds((s, t), I32), protocol_id=sds((s, t), I32),
        leg_proc=sds((s, t, p)), proc_link=sds((s, p, l)),
        leg_link=sds((s, t, l)), bandwidth=sds((s, l)),
        bg_period=sds((s, l), I32), max_ticks=sds((s,), I32),
        leg_valid=sds((s, t), jnp.bool_),
    )


def _compile_fused(sds, s, r, t, p, l, k=32):  # k: the TPU tick window
    state = (
        sds((s, r), I32), sds((s, r), I32), sds((s, r, t)),
        sds((s, r, t), jnp.bool_), sds((s, r, t), jnp.bool_),
        sds((s, r, t), I32), sds((s, r, t), I32), sds((s, r, t)),
        sds((s, r, t)), sds((s, r, l)),
    )
    return grid_tick_bank_fused_pallas.lower(
        state, sds((k, s, r, l)), sds((s, 1, l)), sds((s, 1, l)),
        sds((s, t), I32), sds((s, t), I32), sds((s, l), I32), sds((s,), I32),
        sds((s, t)), sds((s, l)), sds((s, t, p)), sds((s, p, l)),
        sds((s, t, l)),
    ).compile()


@pytest.mark.parametrize("width", [BENCH, PRODUCTION, SEARCH],
                         ids=["bench", "production", "search"])
def test_fused_window_kernel_compiles(sds, width):
    """At the search's widths (pads 512 legs, 384 processes, 128 links) the
    kernel's blocks take 17.1 MiB at 128 rows a tile, over the 16 MiB of
    scoped VMEM: the launch asks for more."""
    _assert_kernel(_compile_fused(sds, *width))


@pytest.mark.parametrize("legs, fits", [(768, True), (1792, True), (1920, False)])
def test_fused_window_kernel_vmem_limit(sds, legs, fits):
    """The fused kernel keeps a scenario's dense incidences in VMEM (the
    ``[T, T]`` dependency block among them). Its launch asks for twice its
    blocks' size, at most 96 MiB, so with one process per leg it fits up to
    1792 padded legs and 1920 is the first width that runs out (ROADMAP
    R1; at the 16 MiB default 768 ran out). Legs-axis tiling should turn
    the last case around."""
    if fits:
        _assert_kernel(_compile_fused(sds, 1, 4, legs, legs, 8))
    else:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            _compile_fused(sds, 1, 4, legs, legs, 8)


@pytest.mark.parametrize("leap", [False, True], ids=["tick", "leap"])
def test_banked_engine_compiles(sds, leap):
    """The whole banked while-loop program of ``Fleet.run``: tick mode
    drives the fused window kernel, leap mode the reference scan over the
    per-tick bank kernel."""
    s, r, t, p, l = BENCH
    params = engine.SimParams(
        keep_frac=sds((s, t)), bg_mu=sds((s, l)), bg_sigma=sds((s, l))
    )
    compiled = engine._simulate_bank_banked.lower(
        _bank_spec(sds, s, t, p, l), params, sds((s, r, 2), jnp.uint32),
        backend="pallas", leap=leap, window=16 if leap else 32,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("leap", [False, True], ids=["tick", "leap"])
def test_banked_engine_compiles_at_widest_kernel_pad(sds, leap):
    """At the widest leg pad the fused kernel admits, the engine program
    still compiles: the XLA-side leg sums grow with log2 of the pad."""
    s, r, t, p, l = 8, 4, 640, 128, 8
    params = engine.SimParams(
        keep_frac=sds((s, t)), bg_mu=sds((s, l)), bg_sigma=sds((s, l))
    )
    compiled = engine._simulate_bank_banked.lower(
        _bank_spec(sds, s, t, p, l), params, sds((s, r, 2), jnp.uint32),
        backend="pallas", leap=leap, window=16 if leap else 32,
    ).compile()
    _assert_kernel(compiled)


def test_search_generation_compiles(sds):
    """The search's simulation at the cell's shape: the banked tick program
    with one ``enabled`` mask per member, the dependency path of the
    placements in the fused kernel."""
    s, r, t, p, l = SEARCH
    params = engine.SimParams(
        keep_frac=sds((s, t)), bg_mu=sds((s, l)), bg_sigma=sds((s, l)),
        enabled=sds((s, r, t), jnp.bool_),
    )
    compiled = engine._simulate_bank_banked.lower(
        _bank_spec(sds, s, t, p, l), params, sds((s, r, 2), jnp.uint32),
        backend="pallas", leap=False, window=32,
    ).compile()
    _assert_kernel(compiled)


def test_fleet_leap_gathers_share_index_across_replicas(sds):
    """The fleet's banked leap program at the production workload's widths,
    one scenario of 512 replica rows: every gather left in the compiled
    event step reads an index without the replica dimension (a broadcast
    ``[S, R, T]`` index lowers to a general gather of ``S * R * T`` index
    tuples, which took most of the event step's device time)."""
    _, _, t, p, l = PRODUCTION
    s, r = 1, 512
    params = engine.SimParams(
        keep_frac=sds((s, r, t)), bg_mu=sds((s, r, l)), bg_sigma=sds((s, r, l))
    )
    compiled = engine._simulate_bank_banked.lower(
        _bank_spec(sds, s, t, p, l), params, sds((s, r, 2), jnp.uint32),
        backend="pallas", leap=True, window=16,
    ).compile()
    _assert_kernel(compiled)
    shapes = gather_index_shapes(compiled.as_text())
    assert shapes, "no gather left in the event step"
    assert all(r not in shape for shape in shapes), shapes


def test_presimulation_batch_compiles(sds):
    """``calibration.presimulate``'s program: the per-campaign leap engine
    vmapped over a batch of theta draws, one ``grid_tick_pallas`` per event."""
    _, _, t, p, l = PRODUCTION
    b = 512
    spec = engine.SimSpec(
        size_mb=sds((t,)), release=sds((t,), I32), dep=sds((t,), I32),
        profile=sds((t,), I32), protocol_id=sds((t,), I32),
        leg_proc=sds((t, p)), proc_link=sds((p, l)), leg_link=sds((t, l)),
        bandwidth=sds((l,)), bg_period=sds((l,), I32), max_ticks=30_000,
    )
    params = engine.SimParams(
        keep_frac=sds((b, t)), bg_mu=sds((b, l)), bg_sigma=sds((b, l))
    )
    compiled = engine._simulate_batch.lower(
        spec, params, sds((b, 2), jnp.uint32),
        backend="pallas", leap=True, window=1,
    ).compile()
    _assert_kernel(compiled)


def test_selu_mlp_forward_compiles(sds):
    cfg = classifier.ClassifierConfig()
    dims = [cfg.in_dim] + [cfg.hidden] * cfg.depth + [1]
    ws = tuple(sds((a, b)) for a, b in zip(dims[:-1], dims[1:]))
    bs = tuple(sds((b,)) for b in dims[1:])
    compiled = selu_mlp_pallas.lower(sds((4096, cfg.in_dim)), ws, bs).compile()
    _assert_kernel(compiled)


def test_classifier_training_step_compiles(sds, monkeypatch):
    """One training epoch — ``jax.value_and_grad`` of the BCE loss through
    the SELU-MLP kernel plus the AdamW update — compiles for the chip."""
    cfg = classifier.ClassifierConfig()
    params = jax.eval_shape(
        lambda k: classifier.init_classifier(k, cfg), jax.random.PRNGKey(0)
    )
    opt = jax.eval_shape(lambda p: adamw_init(p, AdamWConfig(lr=cfg.lr)), params)
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    n, batch = 8192, 4096
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    # the loss resolves its backend while tracing: drop any trace cached
    # under another backend, before and after, so neither leaks
    classifier._train_epoch.clear_cache()
    try:
        compiled = classifier._train_epoch.lower(
            on_chip(params), on_chip(opt), sds((n, 3)), sds((n, 3)),
            sds((n, 0)), sds((2,), jnp.uint32), sds(()), batch_size=batch,
        ).compile()
    finally:
        classifier._train_epoch.clear_cache()
    _assert_kernel(compiled)
