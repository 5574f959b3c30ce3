"""Multi-device integration tests.

These need >1 device, so each test body runs in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process keeps the real single device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(body: str, n_devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        def make_mesh(shape, axes):
            return jax.make_mesh(shape, axes,
                                 axis_types=(AxisType.Auto,) * len(axes))
        """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def test_emem_distributed_read_write():
    out = run_with_devices("""
        from repro.core import emem
        spec = emem.EMemSpec(n_slots=1024, width=4, page_slots=16, n_shards=8)
        mesh = make_mesh((8,), ("data",))
        data = jax.device_put(emem.create(spec),
                              emem.sharding_for(spec, mesh, ("data",)))
        rng = np.random.default_rng(0)
        addrs = jnp.asarray(rng.permutation(1024)[:256].astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(256, 4)).astype(np.float32))
        data = emem.write(spec, mesh, ("data",), data, addrs, vals, 8.0)
        out = emem.read(spec, mesh, ("data",), data, addrs, 8.0)
        assert np.allclose(out, vals), "read-after-write"
        ref = emem.write_ref(spec, emem.create(spec), addrs, vals)
        assert np.allclose(np.asarray(emem.to_logical(spec, data)),
                           np.asarray(ref)), "logical state"
        print("EMEM_OK")
    """)
    assert "EMEM_OK" in out


def test_paged_decode_matches_batch_on_mesh():
    out = run_with_devices("""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=128, kv_layout="paged", kv_page_slots=4,
                          param_dtype="float32", compute_dtype="float32")
        mesh = make_mesh((4, 2), ("data", "model"))
        mesh_ctx.set_context(mesh, batch_axes=("data",), tp_axis="model",
                             kv_axes=("data",))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        B, S = 2, 8
        toks = jnp.asarray(rng.integers(0, 128, (B, S)))
        # paged decode from scratch on the mesh
        cache = model.init_cache(B, 16)
        lengths = jnp.zeros((B,), jnp.int32)
        for t in range(S):
            lengths = lengths + 1
            logits_p, cache = model.decode_step(params, toks[:, t:t+1],
                                                cache, lengths)
        # batch-layout reference without mesh
        mesh_ctx.clear_context()
        cfg_b = dataclasses.replace(cfg, kv_layout="batch")
        mb = Model(cfg_b)
        _, cache_b = mb.prefill(params, {"tokens": toks[:, :-1]}, max_len=16)
        logits_b, _ = mb.decode_step(params, toks[:, -1:], cache_b,
                                     jnp.full((B,), S, jnp.int32))
        err = float(jnp.max(jnp.abs(logits_p[:, :128] - logits_b[:, :128])))
        assert err < 1e-3, err
        print("PAGED_OK", err)
    """)
    assert "PAGED_OK" in out


def test_sharded_training_matches_single_device():
    out = run_with_devices("""
        from repro.models import Model, ModelConfig
        from repro.optim import AdamWConfig
        from repro.train.trainer import TrainConfig, Trainer
        from repro.data import DataConfig, SyntheticLM
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=64, param_dtype="float32",
                          compute_dtype="float32")
        model = Model(cfg)
        data = SyntheticLM(cfg, DataConfig(global_batch=8, seq_len=16))
        losses = []
        for shape, axes in [((8, 1), ("data", "model")),
                            ((4, 2), ("data", "model")),
                            ((1, 1), ("data", "model"))]:
            mesh = make_mesh(shape, axes)
            tr = Trainer(model, mesh, AdamWConfig(lr=1e-3))
            params, opt = tr.init_state(seed=0)
            params, opt, hist = tr.run(params, opt, iter(data), 3)
            losses.append(hist[-1]["loss"])
        assert abs(losses[0] - losses[2]) < 1e-3, losses
        assert abs(losses[1] - losses[2]) < 1e-3, losses
        print("SHARD_OK", losses)
    """)
    assert "SHARD_OK" in out


def test_elastic_restore_across_meshes(tmp_path):
    out = run_with_devices(f"""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager({str(tmp_path)!r}, async_save=False)
        mesh8 = make_mesh((8,), ("data",))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh8, P("data")))
        ckpt.save(1, {{"w": w}})
        # restore onto a 4-device mesh (elastic scale-down)
        mesh4 = make_mesh((4,), ("data",))
        sh = {{"w": NamedSharding(mesh4, P("data"))}}
        restored, step = ckpt.restore({{"w": w}}, shardings=sh)
        assert step == 1
        assert restored["w"].sharding.mesh.shape["data"] == 4
        assert np.allclose(np.asarray(restored["w"]),
                           np.arange(64.0).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_emem_layout_roundtrip_and_overflow_on_meshes():
    """Sharded layout conversion round-trips, and overflowed requests
    (capacity_factor < 1) read back zeros exactly where _plan.valid is
    False, on 1/2/4/8-device meshes."""
    out = run_with_devices("""
        import functools
        from repro.core import emem
        rng = np.random.default_rng(0)
        for shards in (1, 2, 4, 8):
            spec = emem.EMemSpec(n_slots=1024, width=4, page_slots=16,
                                 n_shards=shards)
            mesh = make_mesh((shards,), ("data",))
            sh = emem.sharding_for(spec, mesh, ("data",))
            # round-trip through the physical (device) layout
            logical = jnp.asarray(
                rng.normal(size=spec.global_shape()).astype(np.float32))
            phys = jax.device_put(emem.from_logical(spec, logical), sh)
            back = emem.to_logical(spec, phys)
            assert np.allclose(np.asarray(back), np.asarray(logical)), shards
            # overflow: tight capacity drops exactly the invalid requests
            data = jax.device_put(emem.from_logical(spec, logical), sh)
            addrs = jnp.asarray(rng.integers(0, 1024, 128).astype(np.int32))
            cf = 0.5
            got = np.asarray(emem.read(spec, mesh, ("data",), data, addrs, cf))
            r_shard = 128 // shards
            cap = emem.capacity_for(spec, r_shard, cf)
            flat = np.asarray(logical).reshape(1024, 4)
            for s in range(shards):
                chunk = addrs[s * r_shard:(s + 1) * r_shard]
                valid = np.asarray(emem._plan(spec, chunk, cap).valid)
                if shards == 1:          # single-shard fast path never drops
                    valid = np.ones_like(valid)
                expect = np.where(valid[:, None], flat[np.asarray(chunk)], 0.0)
                assert np.allclose(got[s * r_shard:(s + 1) * r_shard],
                                   expect), (shards, s)
                if shards > 1:
                    assert not valid.all(), "cf=0.5 should overflow"
            print("LAYOUT_OK", shards)
        print("ALL_LAYOUT_OK")
    """)
    assert "ALL_LAYOUT_OK" in out


def test_emem_vm_matches_oracle_on_meshes():
    """EMemVM vread/vwrite match the translated read_ref/write_ref oracle on
    1/2/4/8-device meshes, cache enabled and disabled, incl. after
    free+realloc remapping."""
    out = run_with_devices("""
        from repro.core import emem
        from repro.emem_vm import EMemVM, VMConfig
        for shards in (1, 2, 4, 8):
            spec = emem.EMemSpec(n_slots=1024, width=4, page_slots=16,
                                 n_shards=shards)
            mesh = None if shards == 1 else make_mesh((shards,), ("data",))
            for sets in (0, 4):
                cfg = VMConfig(spec=spec, n_vpages=48, cache_sets=sets)
                vm = EMemVM(cfg, mesh=mesh, axes=("data",))
                vm.map_range(0, 24)
                rng = np.random.default_rng(shards * 10 + sets)
                mirror = np.zeros((1024, 4), np.float32)   # physical slots
                def xlate(addrs):
                    ps = 16
                    phys = np.zeros(len(addrs), np.int64)
                    ok = np.zeros(len(addrs), bool)
                    for i, a in enumerate(addrs):
                        vp = a // ps
                        if vp < 48 and vm.page_table.is_mapped(vp):
                            phys[i] = vm.page_table.frame_of(vp) * ps + a % ps
                            ok[i] = True
                    return phys, ok
                def roundtrip(n_rounds):
                    for _ in range(n_rounds):
                        addrs = rng.choice(48 * 16, 64,
                                           replace=False).astype(np.int32)
                        vals = rng.normal(size=(64, 4)).astype(np.float32)
                        phys, ok = xlate(addrs)
                        vm.vwrite(jnp.asarray(addrs), jnp.asarray(vals))
                        mirror[phys[ok]] = vals[ok]
                        got = np.asarray(vm.vread(jnp.asarray(addrs)))
                        expect = np.where(ok[:, None], mirror[phys], 0.0)
                        assert np.allclose(got, expect, atol=1e-6), \\
                            (shards, sets)
                roundtrip(2)
                for vp in range(0, 24, 2):
                    vm.unmap_page(vp)
                vm.map_range(30, 10)       # recycle freed frames
                roundtrip(2)
                print("VM_OK", shards, sets, vm.counters())
        print("ALL_VM_OK")
    """)
    assert "ALL_VM_OK" in out


def test_vm_valid_bit_swap_matches_oracle_on_meshes():
    """Page-table valid-bit semantics on 1/2/4-device meshes: accesses to
    unmapped pages still fault (read zeros / write dropped), swapped-out
    pages transparently restore through the vread/vwrite fault path, and
    every resident byte matches the read_ref/write_ref oracle through the
    current translation."""
    out = run_with_devices("""
        from repro.core import emem
        from repro.emem_vm import EMemVM, VMConfig
        for shards in (1, 2, 4):
            spec = emem.EMemSpec(n_slots=512, width=4, page_slots=16,
                                 n_shards=shards)
            mesh = None if shards == 1 else make_mesh((shards,), ("data",))
            for sets in (0, 4):
                cfg = VMConfig(spec=spec, n_vpages=24, cache_sets=sets)
                vm = EMemVM(cfg, mesh=mesh, axes=("data",))
                vm.map_range(0, 12)
                rng = np.random.default_rng(shards * 10 + sets)
                ps = 16
                logical = np.zeros((12, ps, 4), np.float32)  # the oracle
                addrs = jnp.asarray(np.arange(12 * ps, dtype=np.int32))
                vals = rng.normal(size=(12 * ps, 4)).astype(np.float32)
                vm.vwrite(addrs, jnp.asarray(vals))
                logical[:] = vals.reshape(12, ps, 4)
                # swap half the pages out: device capacity is released
                free0 = vm.allocator.free_count()
                for vp in range(0, 12, 2):
                    vm.swap_out(vp)
                assert vm.allocator.free_count() == free0 + 6, shards
                assert vm.page_table.swapped_count() == 6
                # reads fault the pages back in and match the oracle
                got = np.asarray(vm.vread(addrs))
                assert np.allclose(got, logical.reshape(-1, 4), atol=1e-6), \\
                    (shards, sets)
                assert vm.page_table.swapped_count() == 0
                assert vm.counters()["swap_ins"] == 6
                # writes to swapped pages fault in too, then land
                vm.swap_out(1)
                w = rng.normal(size=(ps, 4)).astype(np.float32)
                vm.vwrite(jnp.asarray(np.arange(ps, 2 * ps, dtype=np.int32)),
                          jnp.asarray(w))
                logical[1] = w
                # read_ref oracle through the CURRENT translation (frames
                # may have moved across the swap round trip); read_ref
                # takes the logical page order, so undo the device layout
                vm.flush()
                data_log = emem.to_logical(spec, vm.data)
                for vp in range(12):
                    frame = vm.page_table.frame_of(vp)
                    phys = jnp.asarray(frame * ps + np.arange(ps, dtype=np.int32))
                    raw = np.asarray(emem.read_ref(spec, data_log, phys))
                    assert np.allclose(raw, logical[vp], atol=1e-6), \\
                        (shards, sets, vp)
                # unmapped pages still fault: zero reads, dropped writes
                un = jnp.asarray(np.arange(20 * ps, 21 * ps, dtype=np.int32))
                assert not np.asarray(vm.vread(un)).any()
                vm.vwrite(un, jnp.asarray(w))
                assert not np.asarray(vm.vread(un)).any()
                print("SWAP_OK", shards, sets)
        print("ALL_SWAP_OK")
    """)
    assert "ALL_SWAP_OK" in out


@pytest.mark.parametrize("impl", ["composed", "fused"])
def test_pooled_decode_matches_batch_on_mesh(impl):
    """kv_layout="pooled" with scattered frame assignments on a (4 kv) x
    (2 tp) mesh -- the stacked pool carried through the layer scan, its
    page axis sharded, P(None, kv_axes) -- matches the same decode on one
    device, pages and logits, and the batch-layout reference."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.emem_vm.layout import frame_rows
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=128, kv_layout="pooled", kv_page_slots=4,
                          kv_pool_pages=16, param_dtype="float32",
                          compute_dtype="float32", paged_kernel="{impl}")
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        B, S = 2, 8
        toks = jnp.asarray(rng.integers(0, 128, (B, S)))

        def decode(mesh):
            if mesh is not None:
                mesh_ctx.set_context(mesh, batch_axes=("data",),
                                     tp_axis="model", kv_axes=("data",))
            cache = model.init_cache(B, 16)
            # host-managed tables with deliberately scattered frames
            bt = np.full((B, 4), -1, np.int32)
            fl = np.zeros(16, np.int32)
            fr = np.zeros(16, bool)
            alloc = iter([5, 2, 11, 7, 3, 13, 1, 9])
            lengths = jnp.zeros((B,), jnp.int32)
            for t in range(S):
                lengths = lengths + 1
                for b in range(B):
                    lp = t // 4
                    if bt[b, lp] < 0:
                        f = next(alloc); bt[b, lp] = f; fl[f] = lp
                cache["vm"] = {{"block_table": jnp.array(bt),
                               "frame_lpage": jnp.array(fl),
                               "frame_ro": jnp.array(fr)}}
                logits, cache = model.decode_step(params, toks[:, t:t+1],
                                                  cache, lengths)
                jax.block_until_ready(logits)
            mesh_ctx.clear_context()
            return logits, cache["b0"]

        logits_p, pages_p = decode(make_mesh((4, 2), ("data", "model")))
        assert pages_p["k_pages"].shape == (2, 16, 2, 4, 16)
        logits_1, pages_1 = decode(None)
        # frame f sits at row f of one device's pool, and on shard f % 4
        rows = frame_rows(np.arange(16), 16, 4)
        for key in ("k_pages", "v_pages"):
            np.testing.assert_allclose(np.asarray(pages_p[key])[:, rows],
                                       np.asarray(pages_1[key]),
                                       rtol=1e-5, atol=1e-5)
        err1 = float(jnp.max(jnp.abs(logits_p[:, :128] - logits_1[:, :128])))
        assert err1 < 1e-4, err1
        cfg_b = dataclasses.replace(cfg, kv_layout="batch")
        mb = Model(cfg_b)
        _, cache_b = mb.prefill(params, {{"tokens": toks[:, :-1]}}, max_len=16)
        logits_b, _ = mb.decode_step(params, toks[:, -1:], cache_b,
                                     jnp.full((B,), S, jnp.int32))
        err = float(jnp.max(jnp.abs(logits_p[:, :128] - logits_b[:, :128])))
        assert err < 1e-3, err
        print("POOLED_MESH_OK", err1, err)
    """)
    assert "POOLED_MESH_OK" in out


def test_serve_swap_and_cow_token_identity_on_mesh():
    """Host-side page movers (swap-in/out, COW) must permute frame ids into
    the cyclic shard layout's global rows -- regression for the bug where
    ``k_pages[:, frame]`` addressed the wrong physical page on any
    multi-shard mesh.  Swap-preemption and prefix-sharing COW runs must be
    token-identical to their references on a (4 kv) x (2 tp) mesh."""
    out = run_with_devices("""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="pooled",
                           kv_page_slots=4, param_dtype="float32",
                           compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(3, 8))).astype(np.int32)
                   for _ in range(5)]
        system = rng.integers(0, 128, 9).astype(np.int32)
        shp = [np.concatenate([system,
                               rng.integers(0, 128, 2).astype(np.int32)])
               for _ in range(3)]
        def run(pool, mode, ps, share):
            cfg = dataclasses.replace(base, kv_pool_pages=pool)
            mesh = make_mesh((4, 2), ("data", "model"))
            mesh_ctx.set_context(mesh, batch_axes=("data",),
                                 tp_axis="model", kv_axes=("data",))
            model = Model(cfg); params = model.init(jax.random.key(0))
            with ServeEngine(model, params,
                             EngineConfig(slots=5, max_len=32,
                                          preempt_mode=mode)) as e:
                e.blocks.share_prefixes = share
                s = Scheduler(e)
                s.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                          for i, p in enumerate(ps)])
                done = s.run()
            mesh_ctx.clear_context()
            return {r.uid: tuple(r.output) for r in done}, e.shutdown()
        tight, st = run(12, "swap", prompts, False)
        roomy, _ = run(64, "swap", prompts, False)
        assert tight == roomy, (tight, roomy)
        assert st["swapped"] > 0 and st["swap_resumed"] > 0
        assert st["leaked_frames"] == 0
        print("MESH_SWAP_OK", st["swapped"], st["swap_in_pages"])
        shared, st_s = run(24, "swap", shp, True)
        plain, _ = run(24, "swap", shp, False)
        assert shared == plain, (shared, plain)
        assert st_s["cow_copies"] > 0 and st_s["shared_tokens"] > 0
        print("MESH_COW_OK", st_s["cow_copies"])
    """)
    assert "MESH_SWAP_OK" in out and "MESH_COW_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_serve_spill_tier_token_identity_on_meshes(n_devices):
    """Tentpole acceptance on 1/2/4-device meshes: with the host store
    sized to force HOST -> SPILL demotion, spill-resume (incl. two-hop
    promotions) is token-identical per uid to both the recompute baseline
    and a roomy run, and strictly cheaper in decode steps than
    recompute."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="pooled",
                           kv_page_slots=4, param_dtype="float32",
                           compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(3, 8))).astype(np.int32)
                   for _ in range(6)]
        def run(pool, mode, host=None, spill=0):
            cfg = dataclasses.replace(base, kv_pool_pages=pool)
            mesh = make_mesh((n_dev, 1), ("data", "model"))
            mesh_ctx.set_context(mesh, batch_axes=("data",),
                                 tp_axis="model", kv_axes=("data",))
            model = Model(cfg); params = model.init(jax.random.key(0))
            with ServeEngine(model, params,
                             EngineConfig(slots=6, max_len=32,
                                          preempt_mode=mode,
                                          host_frames=host,
                                          spill_frames=spill)) as e:
                e.blocks.share_prefixes = False
                s = Scheduler(e)
                s.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                          for i, p in enumerate(prompts)])
                done = s.run()
            mesh_ctx.clear_context()
            return {{r.uid: tuple(r.output) for r in done}}, e.shutdown()
        spilled, st_sp = run(12, "swap", host=2, spill=32)
        rec, st_rec = run(12, "recompute")
        roomy, _ = run(64, "swap")
        assert spilled == rec == roomy, (spilled, rec, roomy)
        assert st_sp["host_demotions"] > 0 and st_sp["spill_out_pages"] > 0
        assert st_sp["spill_in_pages"] > 0, "no two-hop promotion"
        assert st_sp["decode_steps"] < st_rec["decode_steps"], \\
            (st_sp["decode_steps"], st_rec["decode_steps"])
        assert st_sp["leaked_frames"] == 0
        print("MESH_SPILL_OK", n_dev, st_sp["spill_out_pages"],
              st_sp["decode_steps"], st_rec["decode_steps"])
    """, n_devices=max(n_devices, 2))
    assert "MESH_SPILL_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_serve_host_full_recompute_fallback_on_meshes(n_devices):
    """Satellite acceptance on 1/2/4-device meshes: preempt_mode="swap"
    with a host store deliberately too small and the spill tier DISABLED
    takes the recompute fallback, token-identically to a roomy run (the
    demotion path must not regress the PR 3 fallback when spill is off)."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="pooled",
                           kv_page_slots=4, param_dtype="float32",
                           compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(3, 8))).astype(np.int32)
                   for _ in range(6)]
        def run(pool, host):
            cfg = dataclasses.replace(base, kv_pool_pages=pool)
            mesh = make_mesh((n_dev, 1), ("data", "model"))
            mesh_ctx.set_context(mesh, batch_axes=("data",),
                                 tp_axis="model", kv_axes=("data",))
            model = Model(cfg); params = model.init(jax.random.key(0))
            with ServeEngine(model, params,
                             EngineConfig(slots=6, max_len=32,
                                          preempt_mode="swap",
                                          host_frames=host)) as e:
                e.blocks.share_prefixes = False
                s = Scheduler(e)
                s.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                          for i, p in enumerate(prompts)])
                done = s.run()
            mesh_ctx.clear_context()
            return {{r.uid: tuple(r.output) for r in done}}, e.shutdown()
        tight, st = run(12, 1)
        roomy, _ = run(64, None)
        assert tight == roomy, (tight, roomy)
        assert st["preempted"] > 0 and st["swapped"] == 0
        assert st["spill_out_pages"] == 0 and st["leaked_frames"] == 0
        print("MESH_HOST_FULL_OK", n_dev, st["preempted"])
    """, n_devices=max(n_devices, 2))
    assert "MESH_HOST_FULL_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_serve_token_identity_both_policies_on_meshes(n_devices):
    """The serving determinism test, parametrized over both BlockManager
    policies (kv_layout paged=reserved / pooled=on-demand) on 1/2/4-device
    CPU meshes: identical tokens from the unified block-table path."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="paged", kv_page_slots=4,
                           param_dtype="float32", compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(2, 7))).astype(np.int32)
                   for _ in range(4)]
        outs = {{}}
        for layout in ("paged", "pooled"):
            cfg = dataclasses.replace(
                base, kv_layout=layout,
                kv_pool_pages=16 if layout == "pooled" else None)
            mesh = make_mesh((n_dev, 1), ("data", "model"))
            mesh_ctx.set_context(mesh, batch_axes=("data",),
                                 tp_axis="model", kv_axes=("data",))
            model = Model(cfg)
            params = model.init(jax.random.key(0))
            engine = ServeEngine(model, params,
                                 EngineConfig(slots=2, max_len=32))
            sched = Scheduler(engine)
            sched.submit([Request(uid=i, prompt=p, max_new_tokens=4)
                          for i, p in enumerate(prompts)])
            done = sched.run()
            engine.shutdown()            # leak detector on every mesh
            outs[layout] = {{r.uid: tuple(r.output) for r in done}}
            mesh_ctx.clear_context()
        assert outs["paged"] == outs["pooled"], outs
        print("SERVE_MESH_OK", n_dev)
    """, n_devices=max(n_devices, 2))
    assert "SERVE_MESH_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_sched_reorder_token_identity_on_meshes(n_devices):
    """Residency-aware admission reordering vs FIFO (window=1), across both
    BlockManager policies on 1/2/4-device meshes: per-request greedy tokens
    are identical whatever the admission order, and the reserved (paged)
    policy -- which has no residency signal -- admits in exact FIFO order
    even with a wide window."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import (EngineConfig, Request, ServeEngine,
                                 Scheduler, SchedulerConfig)
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="paged", kv_page_slots=4,
                           param_dtype="float32", compute_dtype="float32")
        rng = np.random.default_rng(0)
        system = rng.integers(0, 128, 8).astype(np.int32)
        prompts = [rng.integers(0, 128, 9).astype(np.int32)] + [
            np.concatenate([system,
                            rng.integers(0, 128, 2).astype(np.int32)])
            for _ in range(3)]
        outs, orders = {{}}, {{}}
        for layout in ("paged", "pooled"):
            for window in (1, 8):
                cfg = dataclasses.replace(
                    base, kv_layout=layout,
                    kv_pool_pages=12 if layout == "pooled" else None)
                mesh = make_mesh((n_dev, 1), ("data", "model"))
                mesh_ctx.set_context(mesh, batch_axes=("data",),
                                     tp_axis="model", kv_axes=("data",))
                model = Model(cfg)
                params = model.init(jax.random.key(0))
                engine = ServeEngine(model, params,
                                     EngineConfig(slots=2, max_len=32))
                order = []
                orig = engine.admit
                engine.admit = lambda r, s: (order.append(r.uid),
                                             orig(r, s))[1]
                sched = Scheduler(engine, SchedulerConfig(window=window))
                sched.submit([Request(uid=i, prompt=p, max_new_tokens=4)
                              for i, p in enumerate(prompts)])
                done = sched.run()
                engine.shutdown()        # leak detector on every mesh
                outs[layout, window] = {{r.uid: tuple(r.output)
                                         for r in done}}
                orders[layout, window] = list(dict.fromkeys(order))
                mesh_ctx.clear_context()
        ref = outs["paged", 1]
        assert all(o == ref for o in outs.values()), outs
        # no residency signal on the static tables: wide window is FIFO
        assert orders["paged", 8] == sorted(orders["paged", 8])
        print("SCHED_MESH_OK", n_dev, orders)
    """, n_devices=max(n_devices, 2))
    assert "SCHED_MESH_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_paged_decode_fused_matches_composed_on_meshes(n_devices):
    """Dispatch-level acceptance for the VM-walking kernels: the fused
    Pallas path (interpret mode on CPU) and the composed-ops oracle return
    byte-identical pages and merged outputs within fp tolerance through the
    same shard_map dispatch, on 1/2/4-way KV sharding -- plus a
    (4 kv) x (2 tp) mesh so the kv_start head-offset path is covered."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import ModelConfig
        from repro.parallel import mesh_ctx
        from repro.parallel.paged_attention import paged_decode_attention
        n_dev = {n_devices}
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=128, kv_layout="pooled",
                          kv_page_slots=8, param_dtype="float32",
                          compute_dtype="float32")
        rng = np.random.default_rng(0)
        B, hkv, hd, n_pages, ps = 2, 2, 16, 16, 8
        q = jnp.asarray(rng.normal(size=(B, 8, hd)).astype(np.float32))
        k_new = jnp.asarray(rng.normal(size=(B, hkv, hd)).astype(np.float32))
        v_new = jnp.asarray(rng.normal(size=(B, hkv, hd)).astype(np.float32))
        kp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd))
                         .astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, hd))
                         .astype(np.float32))
        lengths = jnp.asarray([21, 9], jnp.int32)
        bt = np.full((B, 8), -1, np.int32)
        fl = np.zeros(n_pages, np.int32)
        fr = np.zeros(n_pages, bool)
        alloc = iter([5, 2, 11, 7, 3, 13])    # deliberately scattered
        for b in range(B):
            for lp in range((int(lengths[b]) + ps - 1) // ps):
                f = next(alloc); bt[b, lp] = f; fl[f] = lp
        fr[int(bt[1, 0])] = True              # seq 1 writes a shared page
        vm = {{"block_table": jnp.array(bt), "frame_lpage": jnp.array(fl),
               "frame_ro": jnp.array(fr)}}
        wm = jnp.asarray(np.array([True, True]))
        shapes = [((n_dev, 1), ("data", "model"))]
        if n_dev == 4:
            shapes.append(((4, 2), ("data", "model")))
        for shape, axes in shapes:
            outs = {{}}
            for impl in ("fused", "composed"):
                mesh = make_mesh(shape, axes)
                mesh_ctx.set_context(mesh, batch_axes=("data",),
                                     tp_axis="model", kv_axes=("data",))
                c = dataclasses.replace(cfg, paged_kernel=impl)
                outs[impl] = paged_decode_attention(
                    c, q, k_new, v_new, kp, vp, lengths, vm, wm)
                mesh_ctx.clear_context()
            o_f, kf, vf = outs["fused"]
            o_c, kc, vc = outs["composed"]
            assert np.array_equal(np.asarray(kf), np.asarray(kc)), shape
            assert np.array_equal(np.asarray(vf), np.asarray(vc)), shape
            err = float(jnp.max(jnp.abs(o_f - o_c)))
            assert err < 1e-5, (shape, err)
            print("DISPATCH_FUSED_OK", shape, err)
        print("ALL_DISPATCH_FUSED_OK")
    """, n_devices=max(n_devices * 2, 2))
    assert "ALL_DISPATCH_FUSED_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_serve_fused_kernel_token_identity_on_meshes(n_devices):
    """Tentpole acceptance: the full serving engine with
    ``paged_kernel="fused"`` (the VM-walking Pallas path, interpret mode on
    CPU) produces byte-identical tokens and telemetry to
    ``paged_kernel="composed"`` on 1/2/4-device meshes, under BOTH
    kv_layout policies."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        from repro.serve.telemetry import step_summary
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="pooled",
                           kv_page_slots=4, param_dtype="float32",
                           compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(2, 7))).astype(np.int32)
                   for _ in range(3)]
        for layout in ("pooled", "paged"):
            outs, stats = {{}}, {{}}
            for impl in ("fused", "composed"):
                cfg = dataclasses.replace(
                    base, kv_layout=layout, paged_kernel=impl,
                    kv_pool_pages=16 if layout == "pooled" else None)
                mesh = make_mesh((n_dev, 1), ("data", "model"))
                mesh_ctx.set_context(mesh, batch_axes=("data",),
                                     tp_axis="model", kv_axes=("data",))
                model = Model(cfg)
                params = model.init(jax.random.key(0))
                engine = ServeEngine(model, params,
                                     EngineConfig(slots=2, max_len=32))
                sched = Scheduler(engine)
                sched.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                              for i, p in enumerate(prompts)])
                done = sched.run()
                stats[impl] = engine.shutdown()
                outs[impl] = {{r.uid: tuple(r.output) for r in done}}
                mesh_ctx.clear_context()
            assert outs["fused"] == outs["composed"], (layout, outs)
            assert step_summary(stats["fused"]["telemetry"]) == \\
                step_summary(stats["composed"]["telemetry"]), layout
            print("SERVE_KERNEL_OK", layout)
        print("ALL_SERVE_KERNEL_OK", n_dev)
    """, n_devices=max(n_devices, 2))
    assert "ALL_SERVE_KERNEL_OK" in out


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_fused_decode_token_identity_on_meshes(n_devices):
    """Fused multi-step decode vs step-at-a-time dispatch on 1/2/4-device
    meshes, across both BlockManager policies: identical tokens and
    decode-step telemetry, with strictly fewer Python dispatches when the
    fused while_loop engages."""
    out = run_with_devices(f"""
        import dataclasses
        from repro.models import Model, ModelConfig
        from repro.parallel import mesh_ctx
        from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
        from repro.serve.telemetry import step_summary
        n_dev = {n_devices}
        base = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                           n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=128, kv_layout="pooled",
                           kv_page_slots=8, param_dtype="float32",
                           compute_dtype="float32")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128,
                                int(rng.integers(2, 7))).astype(np.int32)
                   for _ in range(4)]
        for layout in ("pooled", "paged"):
            cfg = dataclasses.replace(
                base, kv_layout=layout,
                kv_pool_pages=16 if layout == "pooled" else None)
            outs, stats = {{}}, {{}}
            for fused in (8, 1):
                mesh = make_mesh((n_dev, 1), ("data", "model"))
                mesh_ctx.set_context(mesh, batch_axes=("data",),
                                     tp_axis="model", kv_axes=("data",))
                model = Model(cfg)
                params = model.init(jax.random.key(0))
                engine = ServeEngine(model, params,
                                     EngineConfig(slots=2, max_len=32,
                                                  max_fused_steps=fused))
                sched = Scheduler(engine)
                sched.submit([Request(uid=i, prompt=p, max_new_tokens=8)
                              for i, p in enumerate(prompts)])
                done = sched.run()
                stats[fused] = engine.shutdown()
                outs[fused] = {{r.uid: tuple(r.output) for r in done}}
                mesh_ctx.clear_context()
            assert outs[8] == outs[1], (layout, outs)
            assert step_summary(stats[8]["telemetry"]) == \\
                step_summary(stats[1]["telemetry"]), layout
            assert stats[8]["dispatches"] < stats[1]["dispatches"], layout
        print("FUSED_MESH_OK", n_dev)
    """, n_devices=max(n_devices, 2))
    assert "FUSED_MESH_OK" in out
