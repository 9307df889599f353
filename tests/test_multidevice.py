"""Multi-device integration tests: run in a subprocess with 8 virtual CPU
devices (the test process itself must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a (2 data, 4 model) mesh computes the same
    loss as the unsharded step."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.parallel import sharding as SH
        from repro.train.optimizer import AdamWConfig

        cfg = get_smoke_config("tinyllama-1.1b")
        opt = AdamWConfig(lr=1e-3)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        state = M.init_train_state(params, opt)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 64)))}

        step = M.make_train_step(cfg, opt)
        _, m_ref = jax.jit(step)(jax.tree.map(jnp.copy, state), batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ssh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.sanitize_specs(SH.tree_specs(state, mesh.axis_names), state, mesh, head_dim=cfg.hd),
                           is_leaf=lambda x: isinstance(x, P))
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.batch_specs(batch, mesh.axis_names),
                           is_leaf=lambda x: isinstance(x, P))
        with mesh:
            sharded = jax.jit(step, in_shardings=(ssh, bsh))
            state_s = jax.device_put(state, ssh)
            batch_s = jax.device_put(batch, bsh)
            _, m_sh = sharded(state_s, batch_s)
        ref, sh = float(m_ref["loss"]), float(m_sh["loss"])
        assert abs(ref - sh) < 1e-3, (ref, sh)
        print("OK", ref, sh)
    """)


@pytest.mark.slow
def test_pipeline_multistage():
    """4-stage pipeline on a 4-device stage mesh == sequential stack."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply

        mesh = jax.make_mesh((4,), ("stage",))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3,
                                   jnp.float32)}
        x = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        out = pipeline_apply(mesh, stage_fn, params, x)
        expected = x
        for s in range(n_stages):
            expected = jnp.tanh(expected @ params["w"][s])
        err = float(jnp.max(jnp.abs(out - expected)))
        assert err < 1e-5, err
        print("OK", err)
    """)


@pytest.mark.slow
def test_decode_step_sharded_kv_cache():
    """Decode with a sequence-sharded KV cache matches the single-device
    decode (SP softmax combine across shards)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.parallel import sharding as SH

        cfg = get_smoke_config("qwen2.5-14b")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        B, L = 8, 64
        cache = M.init_cache(cfg, B, L)
        toks = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (B,)))
        serve = M.make_serve_step(cfg)
        ref_logits, _ = jax.jit(serve)(params, jax.tree.map(jnp.copy, cache), toks)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.sanitize_specs(SH.tree_specs(params, mesh.axis_names), params, mesh, head_dim=cfg.hd),
                           is_leaf=lambda x: isinstance(x, P))
        csh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.sanitize_specs(SH.cache_specs(cache, mesh.axis_names), cache, mesh),
                           is_leaf=lambda x: isinstance(x, P))
        with mesh:
            sharded = jax.jit(serve, in_shardings=(psh, csh, NamedSharding(mesh, P("data"))))
            out, _ = sharded(jax.device_put(params, psh),
                             jax.device_put(cache, csh),
                             jax.device_put(toks, NamedSharding(mesh, P("data"))))
        err = float(jnp.max(jnp.abs(out - ref_logits)))
        assert err < 1e-3, err
        print("OK", err)
    """)


@pytest.mark.slow
def test_bank_shards_over_scenario_axis():
    """simulate_bank with spec/params/keys sharded over the scenario axis on
    an 8-device mesh matches the single-device result — the flattened bank
    batch partitions with zero cross-device structure."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.engine import bank_spec, make_bank_params, simulate_bank
        from repro.core.scenarios import build_bank

        bank = build_bank(n=8, seed=0, max_ticks=20_000)
        params = make_bank_params(bank)
        keys = jax.random.split(jax.random.PRNGKey(0), 16).reshape(8, 2, 2)
        ref = simulate_bank(bank, params, keys, leap=True)

        # Auto axes: the compiler partitions the bank from its input
        # shardings (jax.make_mesh defaults to Explicit axes, which would
        # type every intermediate's sharding instead)
        mesh = jax.make_mesh(
            (8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        shard = lambda a: jax.device_put(
            a, NamedSharding(mesh, P("data", *([None] * (a.ndim - 1)))))
        spec_sh = jax.tree.map(shard, bank_spec(bank))
        params_sh = jax.tree.map(shard, params)
        with jax.set_mesh(mesh):
            out = simulate_bank(spec_sh, params_sh, shard(keys), leap=True)
        for f in ("transfer_time", "conth_mb", "conpr_mb", "done", "ticks"):
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(out, f))
            assert np.allclose(a, b, rtol=1e-5, atol=1e-5), f
        print("OK bank sharded over 8 devices")
    """)


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_mesh_sizes(tmp_path):
    """Fault-tolerance e2e: train 2 steps on a 1-device 'cluster', checkpoint,
    then restore into an 8-device (2x4) mesh with sharded state and continue —
    the elastic-restart path."""
    _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointStore
        from repro.configs import get_smoke_config
        from repro.data.tokens import TokenStream, TokenStreamConfig
        from repro.models import model as M
        from repro.parallel import sharding as SH
        from repro.train.optimizer import AdamWConfig

        ckpt_dir = {str(tmp_path)!r}
        cfg = get_smoke_config("tinyllama-1.1b")
        opt = AdamWConfig(lr=1e-3)
        scfg = TokenStreamConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                 global_batch=8, seed=0)

        # phase 1: "small cluster" (single device), 2 steps, checkpoint
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        state = M.init_train_state(params, opt)
        step = jax.jit(M.make_train_step(cfg, opt))
        stream = TokenStream(scfg)
        for _ in range(2):
            state, m = step(state, {{k: jnp.asarray(v) for k, v in next(stream).items()}})
        store = CheckpointStore(ckpt_dir)
        store.save(2, state)
        loss_small = float(m["loss"])

        # phase 2: "grown cluster" (2x4 mesh), elastic restore + continue
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        template = M.init_train_state(M.init_params(jax.random.PRNGKey(0), cfg), opt)
        ssh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.sanitize_specs(SH.tree_specs(template, mesh.axis_names), template, mesh, head_dim=cfg.hd),
                           is_leaf=lambda x: isinstance(x, P))
        restored, at = store.restore(template, shardings=ssh)
        assert at == 2
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.batch_specs({{"tokens": jnp.zeros((8, 64), jnp.int32)}}, mesh.axis_names),
                           is_leaf=lambda x: isinstance(x, P))
        with mesh:
            sharded_step = jax.jit(M.make_train_step(cfg, opt), in_shardings=(ssh, bsh))
            batch = jax.device_put({{k: jnp.asarray(v) for k, v in next(stream).items()}}, bsh)
            state2, m2 = sharded_step(restored, batch)
        assert int(state2["step"]) == 3
        assert np.isfinite(float(m2["loss"]))
        print("OK elastic restore 1 -> 8 devices; losses", loss_small, float(m2["loss"]))
    """)


@pytest.mark.slow
def test_sharded_simulate_bank_bitwise_parity():
    """shard_map execution (mesh=) is bitwise identical to the unsharded
    run — monolithic (leap on/off, including a non-divisible S that takes
    the in-trace inert-padding path) and bucketed, with stochastic
    background congestion so RNG placement is exercised too."""
    _run("""
        import jax, numpy as np
        from repro.core.engine import make_bank_params, simulate_bank
        from repro.core.scenarios import build_bank

        FIELDS = ("transfer_time", "conth_mb", "conpr_mb", "done", "ticks",
                  "start_tick")

        def check(ref, out, tag):
            for f in FIELDS:
                a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(out, f))
                assert np.array_equal(a, b), (tag, f)

        # monolithic, S=8 over 8 devices
        bank = build_bank(n=8, seed=0, max_ticks=20_000)
        params = make_bank_params(bank, bg_mu=5.0, bg_sigma=2.0)
        keys = jax.random.split(jax.random.PRNGKey(0), 16).reshape(8, 2, 2)
        for leap in (False, True):
            ref = simulate_bank(bank, params, keys, leap=leap, bucketed=False)
            out = simulate_bank(bank, params, keys, leap=leap, bucketed=False,
                                mesh=8)
            check(ref, out, f"mono leap={leap}")

        # S=7 does not divide 8: the engine pads with inert scenarios
        # in-trace and slices them back off
        bank7 = build_bank(n=7, seed=1, max_ticks=20_000)
        params7 = make_bank_params(bank7, bg_mu=5.0, bg_sigma=2.0)
        keys7 = jax.random.split(jax.random.PRNGKey(1), 14).reshape(7, 2, 2)
        ref = simulate_bank(bank7, params7, keys7, leap=True, bucketed=False)
        for d in (3, 8):
            out = simulate_bank(bank7, params7, keys7, leap=True,
                                bucketed=False, mesh=d)
            check(ref, out, f"mono pad mesh={d}")

        # bucketed: per-bucket shard_map dispatch + scatter-back
        bank12 = build_bank(n=12, seed=2, max_ticks=20_000)
        params12 = make_bank_params(bank12, bg_mu=5.0, bg_sigma=2.0)
        keys12 = jax.random.split(jax.random.PRNGKey(2), 24).reshape(12, 2, 2)
        ref = simulate_bank(bank12, params12, keys12, leap=True)
        out = simulate_bank(bank12, params12, keys12, leap=True, mesh=8)
        check(ref, out, "bucketed")
        print("OK sharded bitwise parity")
    """)


@pytest.mark.slow
def test_fleet_sharded_run_and_shard_padded_compile():
    """Fleet(devices=8): compile_bank shard-pads each bucket to a multiple
    of the device count with inert scenarios, the sharded run is bitwise
    equal to an unsharded unpadded fleet, and save/load round-trips the
    padded bank + resolved window."""
    _run("""
        import tempfile
        import jax, numpy as np
        from repro import Fleet
        from repro.core.scenarios import sample_scenarios

        pairs = sample_scenarios(n=12, seed=0)
        plain = Fleet.from_pairs(pairs, n_buckets=4)
        sharded = Fleet.from_pairs(pairs, n_buckets=4, devices=8)
        for b in sharded.bank.buckets:
            assert b.bank.n_scenarios % 8 == 0, b.bank.n_scenarios
            pads = [n for n in b.bank.names if n.startswith("__shard_pad__")]
            assert b.bank.n_scenarios - len(b.scenario_ids) == len(pads)

        key = jax.random.PRNGKey(0)
        ref = plain.run(key=key, replicas=2)
        out = sharded.run(key=key, replicas=2)
        for f in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(out, f))), f

        with tempfile.TemporaryDirectory() as d:
            sharded.save(d)
            loaded = Fleet.load(d)
        assert loaded.window is not None  # resolved window persisted
        for a, b in zip(sharded.bank.buckets, loaded.bank.buckets):
            assert a.bank.n_scenarios == b.bank.n_scenarios
        out2 = loaded.run(key=key, replicas=2, devices=8)
        for f in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(out2, f))), f
        print("OK fleet sharded + save/load")
    """)


@pytest.mark.slow
def test_fleet_stream_prefetch_matches_synchronous():
    """Fleet.stream(prefetch=1) — background compile/transfer of chunk k+1
    while chunk k ticks — yields chunks bitwise equal to the synchronous
    path, and retraces stay 0 after the first chunk."""
    _run("""
        import jax, numpy as np
        from repro import Fleet
        from repro.core import engine as engine_lib
        from repro.core.scenarios import sample_scenarios

        pairs = sample_scenarios(n=12, seed=0)
        fleet = Fleet.from_pairs(pairs)
        kw = dict(chunk=4, key=jax.random.PRNGKey(3), replicas=2)

        sync = list(fleet.stream(iter(pairs), **kw))
        engine_lib.reset_bank_trace_count()
        with engine_lib.count_bank_traces() as first:
            pre = list(fleet.stream(iter(pairs), prefetch=1, **kw))
        assert first.count <= 1, first.count

        assert [c.names for c in sync] == [c.names for c in pre]
        for cs, cp in zip(sync, pre):
            # the stepped loop behind prefetch counts no loop steps
            assert cp.result.steps is None and cs.result.steps is not None
            for f in cs.result._fields:
                if f == "steps":
                    continue
                assert np.array_equal(np.asarray(getattr(cs.result, f)),
                                      np.asarray(getattr(cp.result, f))), f

        with engine_lib.count_bank_traces() as rest:
            list(fleet.stream(iter(pairs), prefetch=2, **kw))
        assert rest.count == 0, rest.count
        print("OK stream prefetch parity, retraces", rest.count)
    """)
