"""Design-space-exploration service tests.

Covers the spec (validation, expansion, feasibility pruning, cache
keys), the batched/bucketed measurement path (bit-identity of the
vmapped telemetry against a Python loop of single-point runs, under the
simulator's donated scan), the shard_map fan-out and its single-host
graceful degradation, the resumable on-disk cache, and the cost/Pareto
post-passes.  Simulation-heavy tests stay on 4x4 arrays with short
phases — the methodology, not the numbers, is under test here.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.dse import (CostModel, ResultCache, SweepPoint, SweepSpec,
                       ascii_frontier, config_hash, frontier_artifact,
                       frontier_ascii, frontier_is_monotone, pareto_front,
                       run_sweep, workload_entries)
from repro.dse.runner import (_bucket_programs, _point_from_record,
                              _point_record)
from repro.mesh import Simulator
from repro.mesh.config import MeshConfig
from repro.mesh.topology import Topology
from repro.mesh.traffic import make_traffic
from repro.netsim_jax.measure import (SweepKey, batched_phased_stats,
                                      clear_sweep_cache, phased_stats)
from repro.netsim_jax.sim import init_state, load_program

PHASES = dict(warmup=50, measure=100, drain=100)


def _digest(mem):
    """``memory_digest`` of a ``(ny, nx, mem_words)`` memory, in numpy."""
    words = (np.asarray(mem, np.int64) % 2**32).astype(np.uint64).ravel()
    place = np.arange(1, words.size + 1, dtype=np.uint64)
    return [int(words.sum() % 2**32),
            int((words * place % 2**32).sum() % 2**32)]


def small_spec(**kw):
    base = dict(nx=4, ny=4, fifo_depths=(2, 4), credits=(4, 16),
                patterns=("uniform",), loads=(0.1, 0.3),
                topologies=("mesh",), name="t", **PHASES)
    base.update(kw)
    return SweepSpec(**base)


# -- spec ---------------------------------------------------------------

class TestSweepSpec:
    def test_expansion_is_cross_product(self):
        spec = small_spec(topologies=("mesh", "torus"),
                          workloads=("allreduce",))
        pts = spec.points()
        # 2 topo x 2 depth x 2 cred x (1 pattern x 2 loads + 1 workload)
        assert len(pts) == 2 * 2 * 2 * 3
        assert len(set(pts)) == len(pts)
        assert pts == spec.points()  # deterministic order

    def test_axes_dedupe_and_sort(self):
        spec = small_spec(fifo_depths=(8, 2, 8), loads=(0.3, 0.1, 0.3))
        assert spec.fifo_depths == (2, 8)
        assert spec.loads == (0.1, 0.3)

    @pytest.mark.parametrize("kw,match", [
        (dict(fifo_depths=()), "fifo_depths"),
        (dict(fifo_depths=(0,)), "fifo_depths"),
        (dict(credits=(-1,)), "credits"),
        (dict(patterns=("nope",)), "unknown traffic pattern"),
        (dict(loads=(0.0,)), "offered loads"),
        (dict(loads=(1.5,)), "offered loads"),
        (dict(workloads=("nope",)), "unknown workload family"),
        (dict(patterns=(), workloads=()), "at least one"),
        (dict(topologies=()), "at least one topology"),
        (dict(topologies=("klein_bottle",)), "unknown topology"),
    ])
    def test_validation_names_the_axis(self, kw, match):
        with pytest.raises(ValueError, match=match):
            small_spec(**kw)

    def test_infeasible_points_pruned_and_reported(self):
        spec = small_spec(fifo_depths=(1, 2, 4),
                          topologies=("mesh", "torus"))
        bad = spec.infeasible()
        assert len(bad) == 1
        topo, depth, why = bad[0]
        assert (topo.spec, depth) == ("torus", 1)
        assert "bubble" in why
        # torus points skip depth 1; mesh keeps it
        assert spec.feasible_depths(Topology.parse("torus")) == (2, 4)
        assert spec.feasible_depths(Topology.parse("mesh")) == (1, 2, 4)

    def test_bucket_capacity_covers_every_point(self):
        spec = small_spec(fifo_depths=(2, 4, 8), credits=(4, 64))
        cfg = spec.bucket_config(spec.topologies[0])
        assert cfg.router_fifo == 8 and cfg.max_out_credits == 64
        key = spec.sweep_key(spec.topologies[0])
        assert isinstance(key, SweepKey)
        assert key.horizon == spec.horizon

    def test_point_key_distinguishes_configs_not_buckets(self):
        spec = small_spec()
        wide = small_spec(fifo_depths=(2, 4, 16))  # bigger bucket capacity
        p = spec.points()[0]
        assert spec.point_key(p) == wide.point_key(p)
        q = SweepPoint(p.nx, p.ny, p.topology, p.fifo_depth, p.credits,
                       p.traffic, p.load, seed=7)
        assert spec.point_key(p) != spec.point_key(q)

    def test_point_keys_and_records_carry_the_deployment(self):
        small, large = small_spec(), small_spec(mem_words=4096, ep_fifo=2,
                                                resp_latency=2)
        topo = large.topologies[0]
        cfg = large.bucket_config(topo)
        assert (cfg.mem_words, cfg.ep_fifo, cfg.resp_latency) == (4096, 2, 2)
        assert large.sweep_key(topo).cfg.mem_words == 4096
        for p, q in zip(small.points(), large.points()):
            assert small.point_key(p) != large.point_key(q)
            assert q.mesh_config() == dataclasses.replace(
                p.mesh_config(), mem_words=4096, ep_fifo=2, resp_latency=2)
            rec = _point_record(q, {}, [0, 0])
            assert rec["point"]["mem_words"] == 4096
            assert _point_from_record(rec) == q
            # a record written before the deployment's sizes were kept
            old = _point_record(p, {}, [0, 0])
            for size in ("ep_fifo", "mem_words", "resp_latency"):
                del old["point"][size]
            assert _point_from_record(old) == p

    @pytest.mark.parametrize("size", ["ep_fifo", "mem_words",
                                      "resp_latency"])
    def test_deployment_sizes_must_be_positive(self, size):
        with pytest.raises(ValueError, match=size):
            small_spec(**{size: 0})

    def test_workload_entries_shapes(self):
        ent = workload_entries("allreduce", 4, 4)
        assert ent["op"].shape[:2] == (4, 4)


# -- batched measurement bit-identity (satellite 3) ---------------------

@pytest.mark.parametrize("topology", ["mesh", "torus"])
def test_batched_phased_stats_matches_loop(topology):
    """Vmapped telemetry under the donated scan must be bit-identical to
    a Python loop of single-point ``phased_stats`` runs, across >=2
    topologies x >=2 fifo depths with distinct credit allowances."""
    depths = [2, 6]
    credits = [8, 24]
    cfg = MeshConfig(nx=4, ny=4, router_fifo=max(depths),
                     max_out_credits=max(credits),
                     topology=Topology.parse(topology)).to_sim()
    key = SweepKey(cfg, **PHASES)
    progs = [load_program(make_traffic("uniform", 4, 4, 40, rate=0.2,
                                       seed=s)) for s in range(4)]
    batched = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *progs)
    out = batched_phased_stats(
        key, batched, fifo_depths=np.array(depths * 2, np.int32),
        max_credits=np.array(credits + credits[::-1], np.int32))
    for i, (d, c) in enumerate(zip(depths * 2, credits + credits[::-1])):
        single = phased_stats(cfg, progs[i], init_state(cfg, d, c), **PHASES)
        for f in single._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f))[i],
                np.asarray(getattr(single, f)),
                err_msg=f"{topology} point {i} field {f}")


def test_clear_sweep_cache_runs():
    clear_sweep_cache()  # idempotent, clears all jit caches
    clear_sweep_cache()


# -- runner -------------------------------------------------------------

class TestRunSweep:
    def test_records_follow_spec_order_and_schema(self, tmp_path):
        spec = small_spec()
        res = run_sweep(spec, cache_dir=tmp_path, chunk=4)
        assert res.n_points == len(spec.points()) == len(res.records)
        assert res.simulated == res.n_points and res.cache_hits == 0
        assert res.buckets == 1  # one topology, one program shape
        for rec, pt in zip(res.records, spec.points()):
            assert rec["point"]["fifo_depth"] == pt.fifo_depth
            assert rec["point"]["topology"] == pt.topology.spec
            assert set(rec["stats"]) >= {"offered", "accepted", "lat_mean",
                                         "hops", "peak_link_util"}

    def test_cache_resume_simulates_nothing(self, tmp_path):
        spec = small_spec()
        r1 = run_sweep(spec, cache_dir=tmp_path, chunk=4)
        r2 = run_sweep(spec, cache_dir=tmp_path, chunk=4)
        assert r2.simulated == 0 and r2.cache_hits == r1.n_points
        assert r2.compiles == 0 and r2.buckets == 0
        assert r1.records == r2.records

    def test_cache_partial_resume(self, tmp_path):
        narrow = small_spec(loads=(0.1,))
        run_sweep(narrow, cache_dir=tmp_path, chunk=4)
        wide = small_spec(loads=(0.1, 0.3))
        r = run_sweep(wide, cache_dir=tmp_path, chunk=4)
        assert r.cache_hits == len(narrow.points())
        assert r.simulated == len(wide.points()) - len(narrow.points())

    def test_sharded_matches_single_device(self):
        spec = small_spec()
        base = run_sweep(spec, cache_dir=None, chunk=4)
        assert base.devices == 1
        ndev = min(2, jax.device_count())
        if ndev < 2:
            pytest.skip("needs >= 2 devices (conftest sets 8)")
        sharded = run_sweep(spec, cache_dir=None, devices=ndev, chunk=4)
        assert sharded.devices == ndev
        assert sharded.records == base.records

    def test_graceful_degradation_warns_and_matches(self):
        """devices > jax.device_count() must fall back to chunked vmap
        with one warning — never a shard_map crash."""
        spec = small_spec(loads=(0.1,))
        base = run_sweep(spec, cache_dir=None, chunk=4)
        with pytest.warns(UserWarning, match="falling back"):
            degraded = run_sweep(spec, cache_dir=None,
                                 devices=jax.device_count() + 1, chunk=4)
        assert degraded.devices == 1
        assert degraded.records == base.records

    def test_deployment_sizes_match_phased_stats_at_that_config(self):
        """Each point of a sweep at a deployment's endpoint and memory
        sizes equals ``phased_stats`` at the point's own MeshConfig, on
        a program addressing words past the default 64, and its memory
        digest sums up the oracle's final memory."""
        spec = small_spec(mem_words=4096, ep_fifo=2, resp_latency=2,
                          loads=(0.2, 0.6), patterns=("uniform", "hotspot"),
                          seed=3)
        length = spec.traffic_length()
        assert length > 64
        res = run_sweep(spec, cache_dir=None, chunk=4)
        assert res.padded_rows == 0
        progs = _bucket_programs(spec, spec.points(), length)
        assert int(progs.buf[:, 1].max()) == length - 1   # the addr lane
        for rec, p in zip(res.records, spec.points()):
            cfg = p.mesh_config().to_sim()
            entries = make_traffic(p.traffic, 4, 4, length, rate=p.load,
                                   seed=p.seed, mem_words=4096)
            want = phased_stats(cfg, load_program(entries), init_state(cfg),
                                **PHASES)
            assert rec["stats"] == {f: round(float(getattr(want, f)), 6)
                                    for f in rec["stats"]}, p.label()
            oracle = Simulator(p.mesh_config(), backend="numpy")
            oracle.attach(entries)
            oracle.run(spec.horizon)
            assert rec["mem_digest"] == _digest(oracle.mem), p.label()

    def test_padded_rows_are_counted(self):
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices (conftest sets 8)")
        spec = small_spec(fifo_depths=(2,), credits=(4,),
                          loads=(0.1, 0.2, 0.3))      # 3 points
        base = run_sweep(spec, cache_dir=None, chunk=4)
        res = run_sweep(spec, cache_dir=None, devices=2, chunk=4)
        assert (base.padded_rows, res.padded_rows) == (0, 1)
        assert res.records == base.records

    def test_infeasible_reported_in_result(self, tmp_path):
        spec = small_spec(fifo_depths=(1, 2), topologies=("torus",))
        res = run_sweep(spec, cache_dir=tmp_path, chunk=4)
        assert len(res.infeasible) == 1 and "fifo_depth=1" in res.infeasible[0]
        assert all(r["point"]["fifo_depth"] >= 2 for r in res.records)


# -- on-disk cache ------------------------------------------------------

class TestResultCache:
    def test_round_trip_and_len(self, tmp_path):
        cache = ResultCache(tmp_path)
        assert len(cache) == 0 and cache.get("k") is None
        cache.put("k", {"stats": {"x": 1.5}})
        assert cache.get("k") == {"stats": {"x": 1.5}}
        assert len(cache) == 1

    def test_disabled_cache(self):
        cache = ResultCache(None)
        cache.put("k", {"a": 1})
        assert cache.get("k") is None and len(cache) == 0

    def test_key_verified_on_read(self, tmp_path):
        cache = ResultCache(tmp_path)
        cache.put("k1", {"a": 1})
        path = cache.path_for("k1")
        hijack = cache.path_for("k2")
        hijack.parent.mkdir(parents=True, exist_ok=True)
        path.rename(hijack)  # wrong content under k2's filename
        assert cache.get("k2") is None  # collision degrades to a miss

    def test_config_hash_is_stable_hex(self):
        h = config_hash()
        assert h == config_hash() and len(h) == 16
        int(h, 16)


# -- cost model ---------------------------------------------------------

class TestCostModel:
    def test_area_scales_with_depth_and_tiles(self):
        cost = CostModel()
        small = MeshConfig(nx=4, ny=4, router_fifo=2)
        deep = MeshConfig(nx=4, ny=4, router_fifo=8)
        big = MeshConfig(nx=8, ny=8, router_fifo=2)
        assert cost.buffer_area_mm2(deep) > cost.buffer_area_mm2(small)
        assert cost.buffer_area_mm2(big) == pytest.approx(
            4 * cost.buffer_area_mm2(small))
        # 2 networks x 5 ports x depth + ep_fifo flits, 160 bits each
        assert cost.tile_buffer_bits(2, ep_fifo=4) == (2 * 5 * 2 + 4) * 160

    def test_energy_accounting(self):
        cost = CostModel()
        assert cost.hop_energy_pj(0) == 0.0
        assert cost.energy_per_packet_pj(100.0, 0.0) == 0.0
        assert cost.energy_per_packet_pj(100.0, 50.0) == pytest.approx(
            2 * 160 * cost.link_pj_per_bit_hop)


# -- pareto -------------------------------------------------------------

class TestPareto:
    def test_front_drops_dominated(self):
        recs = [{"area_mm2": 1.0, "throughput": 0.3},
                {"area_mm2": 2.0, "throughput": 0.2},   # dominated
                {"area_mm2": 2.0, "throughput": 0.5},
                {"area_mm2": 3.0, "throughput": 0.5}]   # dominated (tie y)
        front = pareto_front(recs)
        assert [(r["area_mm2"], r["throughput"]) for r in front] == \
            [(1.0, 0.3), (2.0, 0.5)]
        assert frontier_is_monotone(front)

    def test_front_skips_unsaturated_points(self):
        recs = [{"area_mm2": 1.0, "throughput": None},
                {"area_mm2": 2.0, "throughput": 0.4}]
        assert len(pareto_front(recs)) == 1

    def test_empty_front_is_not_monotone(self):
        assert not frontier_is_monotone([])

    def test_ascii_marks_frontier(self):
        recs = [{"area_mm2": 1.0, "throughput": 0.3},
                {"area_mm2": 2.0, "throughput": 0.2},
                {"area_mm2": 2.0, "throughput": 0.5}]
        fig = ascii_frontier(recs, pareto_front(recs))
        assert fig.count("*") == 2 and "." in fig
        assert "buffer area" in fig


# -- frontier artifact --------------------------------------------------

def test_frontier_artifact_end_to_end(tmp_path):
    spec = small_spec(loads=(0.05, 0.2, 0.4), topologies=("mesh", "torus"))
    res = run_sweep(spec, cache_dir=tmp_path, chunk=8)
    art = frontier_artifact(res)
    assert art["pattern"] == "uniform"
    assert set(art["frontiers"]) == {"mesh", "torus"}
    for f in art["frontiers"].values():
        assert f["frontier"] and f["monotone"]
        assert len(f["points"]) == 4  # 2 depths x 2 credits
        for p in f["points"]:
            assert p["area_mm2"] > 0 and p["throughput"] > 0
    fig = frontier_ascii(art)
    assert "mesh" in fig and "torus" in fig
    # re-pricing uses cached telemetry only — no new simulation
    res2 = run_sweep(spec, cache_dir=tmp_path, chunk=8)
    assert res2.simulated == 0
    cheap = frontier_artifact(res2, cost=CostModel(sram_um2_per_bit=0.1))
    a1 = art["frontiers"]["mesh"]["points"][0]["area_mm2"]
    a2 = cheap["frontiers"]["mesh"]["points"][0]["area_mm2"]
    assert a2 < a1


def test_frontier_requires_synthetic_pattern(tmp_path):
    spec = small_spec(patterns=(), workloads=("broadcast",),
                      fifo_depths=(2,), credits=(4,))
    res = run_sweep(spec, cache_dir=tmp_path, chunk=4)
    with pytest.raises(ValueError, match="workload"):
        frontier_artifact(res)


# -- topology spec strings (tentpole plumbing) --------------------------

class TestTopologyParse:
    @pytest.mark.parametrize("s", ["mesh", "torus", "ring_mesh",
                                   "multi_chip:2:4"])
    def test_round_trip(self, s):
        assert Topology.parse(s).spec == s

    def test_passthrough_and_default_params(self):
        t = Topology.parse("torus")
        assert Topology.parse(t) is t
        assert Topology.parse("multi_chip").spec == "multi_chip:2:4"

    @pytest.mark.parametrize("s,match", [
        ("klein_bottle", "unknown topology"),
        ("mesh:2", "takes no .* parameters"),
        ("multi_chip:x", "ints"),
        ("multi_chip:2:4:8", "at most"),
    ])
    def test_parse_errors(self, s, match):
        with pytest.raises(ValueError, match=match):
            Topology.parse(s)

    def test_min_router_fifo(self):
        assert Topology.parse("mesh").min_router_fifo == 1
        assert Topology.parse("torus").min_router_fifo == 2
        assert Topology.parse("ring_mesh").min_router_fifo == 2


def test_mesh_config_cache_token_distinguishes_fields():
    a = MeshConfig(nx=4, ny=4, router_fifo=2)
    b = MeshConfig(nx=4, ny=4, router_fifo=4)
    c = MeshConfig(nx=4, ny=4, router_fifo=2,
                   topology=Topology.parse("torus"))
    tokens = {cfg.cache_token() for cfg in (a, b, c)}
    assert len(tokens) == 3
    assert a.cache_token() == MeshConfig(nx=4, ny=4,
                                         router_fifo=2).cache_token()
