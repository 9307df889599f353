"""Serving-layer contracts (CONTRACTS.md §8).

Pins the three serving invariants on real workloads:

- **bitwise parity** — a served result equals a direct ``Fleet.run`` of
  the same scenario with the same theta/keys, bit for bit, including
  stochastic replicas and a sharded ``devices=`` server;
- **steady-state retrace budget = 0** — once every pad signature in the
  workload has been probed, >= 50 further admissions with heterogeneous
  campaigns trace nothing;
- **graceful drain** — every submitted request is answered exactly once.

Plus the `BankCheckpoint` error paths (window mismatch, corrupted npz,
resume against different pads) and the slot-template warm store.
"""
import os

import jax
import numpy as np
import pytest

from repro.core import engine
from repro.core.fleet import Fleet
from repro.core.residency import ResidentBank
from repro.core.scenarios import sample_scenarios
from repro.core.workload import compile_campaign
from repro.serve import ServeConfig, SimRequest, SimServer
from repro.serve.cache import pad_signature

# every field a served row carries: the serving path counts no loop steps
# (``SimResult.steps`` stays None there), so ``steps`` is not compared
SERVED_FIELDS = tuple(f for f in engine.SimResult._fields if f != "steps")


def _assert_served_equals_direct(server, rid, grid, campaign, *, theta=None,
                                 keys=None, replicas=1, seed=0):
    """Full bitwise row comparison: rebuild a single-scenario fleet at the
    served signature's pads so every array shape matches exactly."""
    res = server.poll(rid)
    assert res is not None, f"request {rid} not served"
    fleet = Fleet.from_pairs([(grid, campaign)], pad_floors=res.signature)
    if keys is not None:
        direct = fleet.run(theta, keys=np.asarray(keys)[None, :, :])
    else:
        direct = fleet.run(
            theta, replicas=replicas, key=jax.random.PRNGKey(seed)
        )
    assert res.result.steps is None
    for f in SERVED_FIELDS:
        a = np.asarray(getattr(direct, f))[0]
        b = np.asarray(getattr(res.result, f))
        np.testing.assert_array_equal(
            a, b, err_msg=f"request {rid}: field {f!r} diverged"
        )


# ---------------------------------------------------------------------------
# bitwise parity vs direct Fleet.run
# ---------------------------------------------------------------------------
def test_served_bitwise_equals_fleet_run():
    pairs = sample_scenarios(n=6, seed=0, scale=0.5)
    server = SimServer(ServeConfig(slots=4, replicas=1))
    for i, (g, c) in enumerate(pairs):
        server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
    done = server.drain()
    assert sorted(r.rid for r in done) == list(range(6))
    for i, (g, c) in enumerate(pairs):
        _assert_served_equals_direct(server, i, g, c, seed=i)


def test_served_stochastic_replicas_and_theta():
    pairs = sample_scenarios(n=4, seed=2, scale=0.5)
    theta = np.asarray([0.15, 0.4, 0.2], np.float32)
    ks = np.asarray(jax.random.split(jax.random.PRNGKey(7), 4 * 3)).reshape(
        4, 3, 2
    )
    server = SimServer(ServeConfig(slots=4, replicas=3))
    for i, (g, c) in enumerate(pairs):
        server.submit(
            SimRequest(
                rid=i, grid=g, campaign=c, theta=theta, n_replicas=3,
                keys=ks[i],
            )
        )
    server.drain()
    for i, (g, c) in enumerate(pairs):
        _assert_served_equals_direct(
            server, i, g, c, theta=theta, keys=ks[i]
        )
    # and against one combined multi-scenario Fleet.run (union pads): the
    # served rows match on the overlapping extent, padding tails are zero
    # on both sides by the inert-pad contract
    fleet = Fleet.from_pairs(pairs)
    direct = fleet.run(theta, keys=ks)
    for i in range(4):
        served = server.poll(i).result
        for f in SERVED_FIELDS:
            a = np.asarray(getattr(direct, f))[i]
            b = np.asarray(getattr(served, f))
            sl = tuple(slice(0, min(x, y)) for x, y in zip(a.shape, b.shape))
            np.testing.assert_array_equal(a[sl], b[sl], err_msg=f)


def test_mixed_replica_counts_share_a_bank():
    (g1, c1), (g2, c2) = sample_scenarios(n=2, seed=5, scale=0.5)
    server = SimServer(ServeConfig(slots=4, replicas=4))
    server.submit(SimRequest(rid=0, grid=g1, campaign=c1, n_replicas=4, seed=3))
    server.submit(SimRequest(rid=1, grid=g2, campaign=c2, n_replicas=1, seed=4))
    server.drain()
    _assert_served_equals_direct(server, 0, g1, c1, replicas=4, seed=3)
    _assert_served_equals_direct(server, 1, g2, c2, replicas=1, seed=4)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a multi-device host")
def test_sharded_serve_parity():
    n_dev = jax.device_count()
    pairs = sample_scenarios(n=6, seed=1, scale=0.5)
    server = SimServer(
        ServeConfig(slots=n_dev, replicas=2), devices=n_dev
    )
    assert server.mesh is not None
    for i, (g, c) in enumerate(pairs):
        server.submit(SimRequest(rid=i, grid=g, campaign=c, n_replicas=2, seed=i))
    server.drain()
    for i, (g, c) in enumerate(pairs):
        _assert_served_equals_direct(server, i, g, c, replicas=2, seed=i)


def test_sharded_server_rejects_indivisible_slots():
    if jax.device_count() < 2:
        with pytest.raises(ValueError, match="outside 1.."):
            SimServer(ServeConfig(slots=3), devices=2)
    else:
        with pytest.raises(ValueError, match="multiple of the mesh"):
            SimServer(ServeConfig(slots=3), devices=2)


# ---------------------------------------------------------------------------
# steady-state retrace budget
# ---------------------------------------------------------------------------
def test_zero_retraces_after_warmup_across_50_admissions():
    pairs = sample_scenarios(n=58, seed=11, scale=0.5)
    server = SimServer(ServeConfig(slots=4, replicas=1))
    # warm-up: probe one request per pad signature present in the workload
    sig_of = {
        i: pad_signature(compile_campaign(g, c))
        for i, (g, c) in enumerate(pairs)
    }
    probes = {}
    for i, sig in sig_of.items():
        probes.setdefault(sig, i)
    for sig, i in probes.items():
        g, c = pairs[i]
        server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
    server.drain()
    remaining = [i for i in range(len(pairs)) if i not in probes.values()]
    assert len(remaining) >= 50, "workload too homogeneous for the pin"
    with engine.count_bank_traces() as traces:
        for i in remaining:
            g, c = pairs[i]
            server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
            server.step()  # interleave admission with stepping
        server.drain()
    assert traces.count == 0, (
        f"{traces.count} retraces across {len(remaining)} steady-state "
        "admissions — slot admission changed a trace signature"
    )
    # every request answered
    assert all(server.poll(i) is not None for i in range(len(pairs)))


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------
def test_graceful_drain_no_request_lost_or_duplicated():
    pairs = sample_scenarios(n=10, seed=4, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=1))
    for i, (g, c) in enumerate(pairs):
        server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
        server.step()
    first = server.drain()
    assert sorted(r.rid for r in first) == list(range(10))
    # drain is exactly-once: a second drain returns nothing new
    assert server.drain() == []
    m = server.metrics()
    assert m["completed"] == 10 and m["queued"] == 0 and m["resident"] == 0


def test_duplicate_rid_and_replica_overflow_rejected():
    (g, c), = sample_scenarios(n=1, seed=6, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=1))
    server.submit(SimRequest(rid=0, grid=g, campaign=c))
    with pytest.raises(ValueError, match="duplicate request id"):
        server.submit(SimRequest(rid=0, grid=g, campaign=c))
    with pytest.raises(ValueError, match="replicas"):
        server.submit(SimRequest(rid=1, grid=g, campaign=c, n_replicas=3))
    with pytest.raises(KeyError):
        server.poll(999)


def test_drain_stall_raises_instead_of_spinning(monkeypatch):
    """A queued request that can never be admitted (here: every slot
    reported unavailable) must terminate drain with a diagnostic naming the
    stuck rid, not spin silently toward the 1M-round cap."""
    from repro.serve import slots as slots_mod

    (g, c), = sample_scenarios(n=1, seed=11, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=1))
    server.submit(SimRequest(rid=7, grid=g, campaign=c))
    monkeypatch.setattr(slots_mod.SlotBank, "free_slots", lambda self: [])
    with pytest.raises(RuntimeError, match=r"drain stalled.*\[7\]"):
        server.drain()
    assert server.rounds < 10, "stall must be detected immediately"


def test_round_one_rejects_unadmittable_queue_entry():
    """submit() rejects oversized requests before queueing; an entry that
    reaches the queue anyway (external poke) must fail the scheduling round
    loudly instead of being admitted into replica lanes that don't exist."""
    import dataclasses as dc

    (g, c), = sample_scenarios(n=1, seed=12, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=1))
    server.submit(SimRequest(rid=3, grid=g, campaign=c))
    (sig, queue), = server.queues.items()
    pending = queue[0]
    bad_req = dc.replace(pending.admission.request, n_replicas=5)
    queue[0] = pending._replace(
        admission=dc.replace(pending.admission, request=bad_req)
    )
    with pytest.raises(ValueError, match="request 3 asks for 5 replicas"):
        server.drain()


def test_quantize_axis_emits_true_power_of_two_tiers():
    """Regression: a non-power-of-two floor used to leak into the tier
    sequence (quantize_axis(5, 12) == 12, quantize_axis(13, 12) == 24),
    splitting one power-of-two tier across two trace shapes. The floor is
    now rounded up to a power of two before bracketing ``n``."""
    from repro.serve.cache import quantize_axis

    assert quantize_axis(5, 12) == 16
    assert quantize_axis(13, 12) == 16
    assert quantize_axis(17, 12) == 32
    assert quantize_axis(5, 8) == 8
    assert quantize_axis(9, 8) == 16
    assert quantize_axis(1, 1) == 1
    assert quantize_axis(3, 1) == 4
    # every tier is a power of two for any floor
    for floor in (1, 3, 7, 8, 12, 100):
        for n in range(1, 300, 7):
            t = quantize_axis(n, floor)
            assert t >= n and t >= floor and (t & (t - 1)) == 0


def test_metrics_expose_slot_observability():
    pairs = sample_scenarios(n=5, seed=8, scale=0.5)
    server = SimServer(ServeConfig(slots=4, replicas=1))
    for i, (g, c) in enumerate(pairs):
        server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
    server.drain()
    m = server.metrics()
    assert m["submitted"] == m["completed"] == 5
    for bank in m["slot_banks"].values():
        assert 0.0 <= bank["idle_window_fraction"] <= 1.0
        assert bank["occupancy_mean"] <= bank["slots"]
        assert bank["realized_ticks"] > 0
        assert bank["admitted"] == bank["retired"]


# ---------------------------------------------------------------------------
# overlap scheduler: deferred retirement, window ladder, coalescing
# ---------------------------------------------------------------------------
def test_per_request_theta_parity():
    """Deferred retirement with a different theta per request: every served
    row still equals its own direct ``Fleet.run`` bit for bit."""
    pairs = sample_scenarios(n=3, seed=13, scale=0.5)
    thetas = [
        np.asarray([0.1, 0.3, 0.15], np.float32),
        np.asarray([0.25, 0.5, 0.05], np.float32),
        np.asarray([0.4, 0.2, 0.3], np.float32),
    ]
    server = SimServer(ServeConfig(slots=2, replicas=2))
    for i, (g, c) in enumerate(pairs):
        server.submit(
            SimRequest(
                rid=i, grid=g, campaign=c, theta=thetas[i], n_replicas=2,
                seed=i,
            )
        )
    server.drain()
    for i, (g, c) in enumerate(pairs):
        _assert_served_equals_direct(
            server, i, g, c, theta=thetas[i], replicas=2, seed=i
        )


def test_window_ladder_parity_across_rungs():
    """Rung choice is a pure cost knob: the same workload served through
    different window ladders (including a degenerate single-rung one)
    produces bitwise identical results (CONTRACTS.md §7/§8)."""
    pairs = sample_scenarios(n=4, seed=14, scale=0.5)
    results = []
    for rungs in [(8,), (2, 16), (4, 32, 256)]:
        server = SimServer(ServeConfig(slots=2, replicas=1, rungs=rungs))
        for i, (g, c) in enumerate(pairs):
            server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
        server.drain()
        results.append({i: server.poll(i).result for i in range(len(pairs))})
        for i, (g, c) in enumerate(pairs):
            _assert_served_equals_direct(server, i, g, c, seed=i)
    base = results[0]
    for other in results[1:]:
        for i, res in base.items():
            for f in res._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(res, f)),
                    np.asarray(getattr(other[i], f)),
                    err_msg=f"rid {i}: field {f!r} diverged across rungs",
                )


def test_coalesced_uptier_slice_parity():
    """A request whose native bank is cold routes up-tier into an existing
    wider bank (dominating signature) and its retired slice — cut back to
    native pads — is still bitwise the native-pads ``Fleet.run``."""
    pairs = sample_scenarios(n=12, seed=15, scale=0.5)
    sigs = [pad_signature(compile_campaign(g, c)) for g, c in pairs]
    wide_i = narrow_i = None
    for i, a in enumerate(sigs):
        for j, b in enumerate(sigs):
            if a != b and all(x >= y for x, y in zip(a, b)):
                wide_i, narrow_i = i, j
                break
        if wide_i is not None:
            break
    assert wide_i is not None, "workload has no dominating signature pair"
    server = SimServer(
        ServeConfig(slots=4, replicas=1, coalesce_ratio=1e9)
    )
    server.submit(
        SimRequest(rid=0, grid=pairs[wide_i][0], campaign=pairs[wide_i][1],
                   seed=0)
    )
    server.drain()
    assert list(server.banks) == [sigs[wide_i]]
    server.submit(
        SimRequest(rid=1, grid=pairs[narrow_i][0],
                   campaign=pairs[narrow_i][1], seed=1)
    )
    server.drain()
    # the narrow request never built its own bank — it ran up-tier
    assert list(server.banks) == [sigs[wide_i]]
    assert server.coalesced == 1
    m = server.metrics()
    (bank_m,) = m["slot_banks"].values()
    assert bank_m["coalesced_in"] == 1
    res = server.poll(1)
    assert res.signature == sigs[narrow_i], "served signature must be native"
    _assert_served_equals_direct(
        server, 1, pairs[narrow_i][0], pairs[narrow_i][1], seed=1
    )


def test_trace_budget_is_rungs_plus_two_per_bank():
    """The whole dispatch set is traced at bank construction: exactly
    ``len(rungs) + 2`` traces per pad signature (admission merge + one
    window step per rung + snapshot), and zero afterwards no matter how
    requests, rungs, or admissions interleave."""
    pairs = sample_scenarios(n=10, seed=16, scale=0.5)
    engine.reset_bank_trace_count(clear_caches=True)
    server = SimServer(
        ServeConfig(slots=3, replicas=2, rungs=(8, 64), coalesce=False)
    )
    with engine.count_bank_traces() as probe:
        for i, (g, c) in enumerate(pairs[:4]):
            server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
        server.drain()
    expected = len(server.banks) * (len(server.rungs) + 2)
    assert probe.count == expected, (
        f"{probe.count} traces for {len(server.banks)} banks with "
        f"{len(server.rungs)} rungs — budget is rungs + 2 per signature"
    )
    with engine.count_bank_traces() as steady:
        for i, (g, c) in enumerate(pairs[4:], start=4):
            server.submit(SimRequest(rid=i, grid=g, campaign=c, seed=i))
            server.step()
        server.drain()
    new_banks = len(server.banks) * (len(server.rungs) + 2) - expected
    assert steady.count == new_banks, (
        f"{steady.count} steady-state traces ({new_banks} budgeted for "
        "banks first built in the steady phase)"
    )
    assert all(server.poll(i) is not None for i in range(len(pairs)))


def test_unused_replica_lanes_are_inert():
    """An ``n_replicas=1`` request on a ``replicas=4`` server leaves lanes
    1..3 born-done: they never tick (no compute, no RNG draws), while the
    real lane runs — and the retired ``[n_replicas, ...]`` slice still
    matches the direct run."""
    (g, c), = sample_scenarios(n=1, seed=17, scale=0.5)
    server = SimServer(ServeConfig(slots=2, replicas=4))
    server.submit(SimRequest(rid=0, grid=g, campaign=c, n_replicas=1, seed=0))
    server.drain()
    res = server.poll(0)
    (bank,) = server.banks.values()
    _version, _live, full = bank._seen
    ticks = np.asarray(full.ticks)  # [S, R]
    assert ticks[res.slot, 0] > 0, "the real replica lane must have run"
    assert ticks[res.slot, 1:].max() == 0, (
        "unused replica lanes ticked — they must be born-done inert"
    )
    _assert_served_equals_direct(server, 0, g, c, replicas=1, seed=0)


# ---------------------------------------------------------------------------
# warm store
# ---------------------------------------------------------------------------
def test_warm_dir_roundtrip(tmp_path):
    warm = str(tmp_path / "warm")
    (g, c), = sample_scenarios(n=1, seed=9, scale=0.5)
    s1 = SimServer(ServeConfig(slots=2, warm_dir=warm))
    s1.submit(SimRequest(rid=0, grid=g, campaign=c, seed=0))
    s1.drain()
    assert s1.cache.warm_loads == 0 and os.listdir(warm)
    s2 = SimServer(ServeConfig(slots=2, warm_dir=warm))
    s2.submit(SimRequest(rid=0, grid=g, campaign=c, seed=0))
    s2.drain()
    assert s2.cache.warm_loads == 1
    _assert_served_equals_direct(s2, 0, g, c, seed=0)


# ---------------------------------------------------------------------------
# ResidentBank ownership rules
# ---------------------------------------------------------------------------
def test_resident_bank_is_shared_and_write_protected():
    (g, c), = sample_scenarios(n=1, seed=10, scale=0.5)
    fleet = Fleet.from_pairs([(g, c)])
    res = fleet.resident
    assert res is fleet.resident  # memoized per bank
    # immutable residents share engine.bank_spec's device buffers
    assert res.spec.size_mb is engine.bank_spec(fleet.bank).size_mb
    with pytest.raises(ValueError, match="immutable ResidentBank"):
        res.write_rows([0], fleet.bank)
    mutable = ResidentBank(fleet.bank, mutable=True)
    other = Fleet.from_pairs([(g, c)], pad_floors=(12, 12, 12)).bank
    with pytest.raises(ValueError, match="differ from resident pads"):
        mutable.write_rows([0], other)


# ---------------------------------------------------------------------------
# BankCheckpoint error paths (window mismatch / corruption / wrong pads)
# ---------------------------------------------------------------------------
def _checkpointed_run(fleet, keys, window=4):
    cks = []
    engine.simulate_bank_stepped(
        fleet.bank, fleet.params(), keys, window=window,
        checkpoint_every=1, on_checkpoint=cks.append,
    )
    assert cks, "run finished before the first checkpoint"
    return cks[0]


def test_checkpoint_window_mismatch_rejected(tmp_path):
    pairs = sample_scenarios(n=2, seed=0, scale=0.5)
    fleet = Fleet.from_pairs(pairs)
    keys = jax.random.split(jax.random.PRNGKey(0), 2).reshape(2, 1, 2)
    ck = _checkpointed_run(fleet, keys, window=4)
    with pytest.raises(ValueError, match="cannot[\\s]+resume at window"):
        engine.simulate_bank_stepped(
            fleet.bank, fleet.params(), keys, window=8, resume=ck
        )


def test_checkpoint_corrupted_npz_rejected(tmp_path):
    pairs = sample_scenarios(n=2, seed=0, scale=0.5)
    fleet = Fleet.from_pairs(pairs)
    keys = jax.random.split(jax.random.PRNGKey(0), 2).reshape(2, 1, 2)
    ck = _checkpointed_run(fleet, keys)
    path = str(tmp_path / "ck")
    fleet.save_checkpoint(path, ck)
    # truncate the carry payload
    with open(os.path.join(path, "carry.npz"), "wb") as f:
        f.write(b"PK\x03\x04 truncated")
    with pytest.raises(ValueError, match="truncated/corrupted"):
        Fleet.load_checkpoint(path)
    # and a missing directory names the path it could not read
    with pytest.raises(ValueError, match="cannot read checkpoint metadata"):
        Fleet.load_checkpoint(str(tmp_path / "missing"))


def test_checkpoint_resume_against_different_pads_rejected():
    pairs = sample_scenarios(n=2, seed=0, scale=0.5)
    fleet = Fleet.from_pairs(pairs)
    keys = jax.random.split(jax.random.PRNGKey(0), 2).reshape(2, 1, 2)
    ck = _checkpointed_run(fleet, keys)
    other = Fleet.from_pairs(pairs, pad_floors=(12, 12, 12))
    with pytest.raises(ValueError, match="different pads"):
        engine.simulate_bank_stepped(
            other.bank, other.params(), keys, window=4, resume=ck
        )
    # replica-count mismatch is caught by the same validation
    keys3 = jax.random.split(jax.random.PRNGKey(0), 6).reshape(2, 3, 2)
    with pytest.raises(ValueError, match="different pads"):
        engine.simulate_bank_stepped(
            fleet.bank, fleet.params(), keys3, window=4, resume=ck
        )
