"""Distributed FliX + sharded train step on 8 fake host devices.

These run in subprocesses so the main test process keeps its single real
device (smoke tests must not see 512 devices — launcher contract)."""

import pytest

from tests.conftest import run_with_devices

pytestmark = pytest.mark.slow  # subprocess multi-device runs


def test_shard_apply_ops_end_to_end():
    """Mixed batch through shard_apply_ops == dict model, both routings,
    on a model-checked insert → delete → read sequence (8 shards)."""
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro import core
        from repro.core import ExecConfig, distributed as dist

        mesh = dist.make_shard_mesh(8)
        rng = np.random.default_rng(11)
        universe = rng.permutation(200000).astype(np.int32)
        keys, extra = universe[:4000], universe[4000:6000]
        vals = np.arange(4000, dtype=np.int32)
        sk = np.sort(keys); sv = vals[np.argsort(keys)]
        model = dict(zip(keys.tolist(), vals.tolist()))

        idx = dist.shard_build(jnp.asarray(sk), jnp.asarray(sv), mesh, node_size=16, nodes_per_bucket=8)

        # one mixed batch: insert `extra`, delete a third of `keys`, and
        # read points + successors in the same step (update-then-read)
        dels = keys[::3]
        n_pt, n_sc = 400, 200
        pts = rng.integers(0, 200000, n_pt).astype(np.int32)
        sq = rng.integers(0, 200001, n_sc).astype(np.int32)
        tags = np.concatenate([
            np.full(extra.shape, core.OP_INSERT), np.full(dels.shape, core.OP_DELETE),
            np.full(n_pt, core.OP_POINT), np.full(n_sc, core.OP_SUCCESSOR)]).astype(np.int32)
        bk = np.concatenate([extra, dels, pts, sq]).astype(np.int32)
        bv = np.zeros(bk.shape, np.int32); bv[:extra.shape[0]] = np.arange(extra.shape[0]) + 500000
        ops, perm = core.make_ops(tags, bk, bv, pad_to=4096)
        for k, v in zip(extra, bv[:extra.shape[0]]): model[int(k)] = int(v)
        for k in dels: del model[int(k)]
        live = np.array(sorted(model))
        EMPTY = np.iinfo(np.int32).max

        for routing in ("replicated", "a2a"):
            _, res, stats = dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig(routing=routing))
            assert int(stats["inserted"]) == extra.shape[0]
            assert int(stats["deleted"]) == dels.shape[0]
            value = np.asarray(core.unsort(res["value"], perm[:bk.shape[0]]))
            skk = np.asarray(core.unsort(res["succ_key"], perm[:bk.shape[0]]))
            o = extra.shape[0] + dels.shape[0]
            for i, q in enumerate(pts):
                assert value[o + i] == model.get(int(q), -1), (q, value[o + i])
            for i, q in enumerate(sq):
                j = np.searchsorted(live, q)
                want = live[j] if j < len(live) else EMPTY
                assert skk[o + n_pt + i] == want, (q, skk[o + n_pt + i], want)
                if j < len(live):
                    assert value[o + n_pt + i] == model[int(live[j])]
            print(f"{routing} ok")
        print("DIST_ENGINE_OK")
        """
    )
    assert "DIST_ENGINE_OK" in out


def test_shard_apply_ops_a2a_overflow_surfaced():
    """Skewed batch over a tight per-pair capacity reports overflow; the
    re-route with a larger capacity matches replicated byte-for-byte."""
    out = run_with_devices(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro import core
        from repro.core import ExecConfig, distributed as dist

        mesh = dist.make_shard_mesh(8)
        rng = np.random.default_rng(13)
        keys = np.sort(rng.permutation(100000)[:4000]).astype(np.int32)
        idx = dist.shard_build(jnp.asarray(keys), jnp.asarray(keys), mesh, node_size=16, nodes_per_bucket=8)

        hi = int(np.asarray(idx.part_fences)[0])  # everything -> shard 0
        q = rng.integers(0, hi, 2048).astype(np.int32)
        ops, perm = core.make_ops(np.full(2048, core.OP_POINT, np.int32), q)
        _, _, stats = dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig(routing="a2a", capacity=64))
        assert int(stats["a2a_overflow"]) == 2048 - 8 * 64, int(stats["a2a_overflow"])
        _, res, stats = dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig(routing="a2a", capacity=256))
        assert int(stats["a2a_overflow"]) == 0
        _, want, _ = dist.shard_apply_ops(idx, ops, mesh, config=ExecConfig(routing="replicated"))
        assert (np.asarray(res["value"]) == np.asarray(want["value"])).all()
        print("A2A_OVERFLOW_OK")
        """
    )
    assert "A2A_OVERFLOW_OK" in out


def test_sharded_kv_index_subprocess():
    """KVPageIndex(shards=4): engine-served pages_of across the mesh."""
    out = run_with_devices(
        """
        import numpy as np
        from repro.serve.kv_index import KVPageIndex

        kv = KVPageIndex(shards=4)
        seqs = np.arange(6)
        kv.allocate(seqs, np.zeros(6, int), seqs * 10)
        kv.allocate(seqs, np.ones(6, int), seqs * 10 + 1)
        assert (np.asarray(kv.lookup(seqs, np.ones(6, int))) == seqs * 10 + 1).all()
        pg, sl, cnt = kv.pages_of(2)
        assert int(cnt) == 2 and np.asarray(sl)[:2].tolist() == [20, 21]
        kv.free_sequences([2])
        assert kv.live_pages() == 10
        print("KV_SHARDED_OK")
        """
    )
    assert "KV_SHARDED_OK" in out


def test_sharded_train_step_runs_and_matches_single_device():
    """Same model, same data: 4x2-sharded loss == single-device loss."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.model import get_config
        from repro.train import make_train_step, train_state_init, TrainState
        from repro.optim import AdamWState
        from repro import sharding as sh

        cfg = get_config("h2o-danube-3-4b").reduced(dtype="float32")
        rng = jax.random.PRNGKey(0)
        state = train_state_init(rng, cfg)
        tokens = jax.random.randint(rng, (8, 64), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": tokens}
        step = make_train_step(cfg, loss_chunk=16)

        _, m1 = jax.jit(step)(state, batch)
        loss_single = float(m1["loss"])

        from repro.launch.mesh import make_mesh_auto
        mesh = make_mesh_auto((4, 2), ("data", "model"))
        pspecs = sh.param_specs(cfg, state.params, tp=2)
        sspecs = TrainState(params=pspecs, opt=AdamWState(step=P(), m=pspecs, v=pspecs))
        ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        with mesh:
            jstep = jax.jit(step, in_shardings=(ns(sspecs), ns(sh.input_specs_sharding(mesh, batch))))
            _, m2 = jstep(state, batch)
        loss_sharded = float(m2["loss"])
        assert abs(loss_single - loss_sharded) < 1e-3, (loss_single, loss_sharded)
        print("SHARDED_TRAIN_OK", loss_single, loss_sharded)
        """
    )
    assert "SHARDED_TRAIN_OK" in out


def test_tiny_dryrun_cell_compiles():
    """build_cell lowers + compiles on an 8-device mesh (dryrun smoke)."""
    out = run_with_devices(
        """
        import jax
        from repro.launch.steps import build_cell
        import repro.models.config as mc
        import dataclasses

        # shrink the shape table so the tiny mesh compiles fast
        mc.SHAPES["train_4k"] = dict(kind="train", seq_len=256, global_batch=8)
        mc.SHAPES["decode_32k"] = dict(kind="decode", seq_len=512, global_batch=8)
        from repro.launch.mesh import make_mesh_auto
        mesh = make_mesh_auto((4, 2), ("data", "model"))
        import repro.models.model as mm
        from repro.models.model import get_config
        real = get_config("musicgen-medium").reduced(dtype="bfloat16")
        import repro.configs as configs
        configs.REGISTRY["musicgen-medium"] = real
        with mesh:
            for shape in ("train_4k", "decode_32k"):
                cell = build_cell("musicgen-medium", shape, mesh, loss_chunk=64)
                compiled = cell.jitted.lower(*cell.abstract_args).compile()
                assert compiled.cost_analysis() is not None
        print("DRYRUN_SMOKE_OK")
        """
    )
    assert "DRYRUN_SMOKE_OK" in out


def test_gradient_compression_error_feedback():
    """int8 EF quantizer: accumulated quantized grads ≈ true sum over steps."""
    import jax.numpy as jnp
    import numpy as np

    from repro.optim import compress_init, decompress_add, quantize_grads

    rng = np.random.default_rng(5)
    params = {"w": jnp.zeros((64, 64))}
    state = compress_init(params)
    true_sum = np.zeros((64, 64), np.float32)
    acc = {"w": jnp.zeros((64, 64))}
    for i in range(16):
        g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
        true_sum += np.asarray(g["w"])
        q8, scales, state = quantize_grads(g, state)
        assert q8["w"].dtype == jnp.int8  # 4× fewer bytes on the wire
        acc = decompress_add(acc, q8, scales)
    rel = np.abs(np.asarray(acc["w"]) - true_sum).max() / np.abs(true_sum).max()
    assert rel < 0.02, rel


def test_moe_a2a_matches_dense_oracle():
    """shard_map all-to-all MoE dispatch (§Perf iteration 4): exact vs the
    dense oracle, including virtual-expert split and gradients."""
    out = run_with_devices(
        """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.model import get_config
        from repro.models.moe import moe_ffn_dense_oracle
        from repro.models.moe_a2a import moe_ffn_a2a

        from repro.launch.mesh import make_mesh_auto
        mesh = make_mesh_auto((4, 2), ("data", "model"))
        cfg = get_config("deepseek-moe-16b").reduced(dtype="float32", moe_capacity_factor=8.0)
        cfg = dataclasses.replace(cfg, num_experts=4, top_k=2)
        D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
        k = jax.random.split(jax.random.PRNGKey(4), 8)
        p = {
            "router": jax.random.normal(k[0], (D, E)) * 0.1,
            "w_gate": jax.random.normal(k[1], (E, D, F)) * 0.05,
            "w_up": jax.random.normal(k[2], (E, D, F)) * 0.05,
            "w_down": jax.random.normal(k[3], (E, F, D)) * 0.05,
            "shared_gate": jax.random.normal(k[4], (D, F)) * 0.05,
            "shared_up": jax.random.normal(k[5], (D, F)) * 0.05,
            "shared_down": jax.random.normal(k[6], (F, D)) * 0.05,
        }
        cfg = dataclasses.replace(cfg, num_shared_experts=1)
        x = jax.random.normal(k[7], (64, D))
        with mesh:
            got = jax.jit(lambda x, p: moe_ffn_a2a(x, p, cfg, mesh))(x, p)
        want = moe_ffn_dense_oracle(x, p, cfg)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 2e-4, err

        # split=2 virtual experts, same math
        cfg2 = dataclasses.replace(cfg, moe_split=2)
        def split_w(w, axis):
            a, b = jnp.split(w, 2, axis=axis)
            return jnp.stack([a, b], axis=1).reshape((E * 2,) + a.shape[1:])
        p2 = dict(p)
        p2["w_gate"] = split_w(p["w_gate"], 2)
        p2["w_up"] = split_w(p["w_up"], 2)
        p2["w_down"] = split_w(p["w_down"], 1)
        with mesh:
            got2 = jax.jit(lambda x, p: moe_ffn_a2a(x, p, cfg2, mesh))(x, p2)
        assert float(jnp.max(jnp.abs(got2 - want))) < 2e-4

        # differentiable end to end
        def loss(p, x):
            return jnp.sum(moe_ffn_a2a(x, p, cfg, mesh) ** 2)
        with mesh:
            g = jax.jit(jax.grad(loss))(p, x)
        gn = float(jnp.sqrt(sum(jnp.sum(v.astype(jnp.float32)**2) for v in jax.tree.leaves(g))))
        assert np.isfinite(gn) and gn > 0
        print("MOE_A2A_OK")
        """
    )
    assert "MOE_A2A_OK" in out
