"""One benchmark per paper table/figure. Each returns a list of CSV rows
(name, value, derived) and asserts the paper's headline claim."""
from __future__ import annotations

import time

import numpy as np

import math

from repro.core import cost_model as cm
from repro.core import dpa, protocol
from repro.core.engine import simulate_multi_job, sweep_fsdp_contention
from repro.core.simulator import (FabricParams, WorkerParams, simulate_allgather,
                                  simulate_broadcast, sweep_phase_breakdown)
from repro.core.topology import FatTree, Torus2D

GIB = 1 << 30
ROWS = list


def fig2_traffic_model():
    """Fig 2: theoretical bandwidth savings, 1024-node fat-tree, radix 32."""
    tree = FatTree(k=32, n_hosts=1024)
    n = 1 << 20
    rows = []
    ring = cm.p2p_ring_allgather_traffic(tree, 1024, n)
    mc_ag = cm.mcast_allgather_traffic(tree, 1024, n)
    kno = cm.p2p_knomial_bcast_traffic(tree, 1024, n, k=4)
    mc_bc = cm.mcast_bcast_traffic(tree, 1024, n)
    rows.append(("fig2.allgather_ring_bytes", ring, f"x{ring/mc_ag:.2f} vs mcast"))
    rows.append(("fig2.allgather_mcast_bytes", mc_ag, "every byte crosses each link once"))
    rows.append(("fig2.bcast_knomial_bytes", kno, f"x{kno/mc_bc:.2f} vs mcast"))
    rows.append(("fig2.bcast_mcast_bytes", mc_bc, "bandwidth-optimal"))
    assert 1.5 <= ring / mc_ag <= 2.5, "paper: ~2x traffic reduction"
    return rows


def fig5_cpu_datapath():
    """Fig 5: single CPU core vs single multithreaded DPA core at 200 Gbit/s."""
    link = dpa.LINK_200G_BYTES
    rows = []
    for name, gib in dpa.CPU_CORE_TPUT_GIB.items():
        rows.append((f"fig5.cpu_core.{name}_gibs", gib,
                     f"{gib*GIB/link*100:.0f}% of 200G link"))
        assert gib * GIB < link  # CPU core cannot sustain the link
    d = dpa.sustained_tput(dpa.DpaConfig("UD", 16)) / GIB
    rows.append(("fig5.dpa_core16t_UD_gibs", round(d, 2), "scales to peak"))
    assert d * GIB >= 0.99 * link
    return rows


def fig10_critical_path():
    """Fig 10: protocol phase breakdown vs scale and message size."""
    rows = []
    data = sweep_phase_breakdown(
        sizes=[4096, 1 << 17, 4 << 20], nodes=[2, 16, 188], seed=0
    )
    for r in data:
        rows.append((
            f"fig10.P{r['nodes']}.{r['bytes']}B.mcast_frac",
            round(r["mcast_frac"], 4),
            f"rnr={r['rnr_frac']:.3f} rel={r['reliability_frac']:.3f}",
        ))
    big = next(r for r in data if r["nodes"] >= 16 and r["bytes"] == 4 << 20)
    assert big["mcast_frac"] > 0.99, "paper: 99% of time in data movement at 16+ nodes"
    return rows


def fig11_throughput_188():
    """Fig 11: per-rank receive throughput at 188 nodes (56 Gbit/s CX-3)."""
    fab = FabricParams(b_link=56e9 / 8)
    wk = WorkerParams(n_recv_workers=2, thread_tput=9.0 * GIB)
    rng = np.random.default_rng(0)
    rows = []
    p = 188
    for size in (1 << 14, 1 << 17, 1 << 20):
        ag = simulate_allgather(p, size, fab, wk, rng)
        t_ring = cm.allgather_time_ring(size, fab.b_link, p)
        ring_tput = (p - 1) * size / t_ring
        rows.append((f"fig11.allgather.{size}B.mcast_GBs",
                     round(ag.per_rank_recv_tput / 1e9, 3),
                     f"ring={ring_tput/1e9:.3f} GB/s (both receive-bound)"))
        # paper: mcast ~ ring for 128-256 KiB (receive-bound alignment)
        if size == 1 << 17:
            assert 0.5 < ag.per_rank_recv_tput / ring_tput < 1.5
    n = 8 << 20  # paper reports the tree-vs-mcast gaps at large messages
    t_mc = cm.bcast_time_multicast(n, fab.b_link, p)
    t_kno = cm.bcast_time_knomial(n, fab.b_link, p)
    t_bin = cm.bcast_time_binary_tree(n, fab.b_link, p)
    rows.append(("fig11.bcast.mcast_vs_knomial_x", round(t_kno / t_mc, 2),
                 "paper: up to 1.3x"))
    rows.append(("fig11.bcast.mcast_vs_binary_x", round(t_bin / t_mc, 2),
                 "paper: up to 4.75x (ours is the store-and-forward bound)"))
    assert 1.05 < t_kno / t_mc < 1.8
    assert t_bin / t_mc > 3.0
    return rows


def fig12_traffic_savings():
    """Fig 12: switch-port counter savings on the 188-node, 18-switch testbed."""
    tree = FatTree(k=16, n_hosts=188)
    n = 1 << 16  # 64 KiB per the paper's counter experiment
    rows = []
    ring = cm.p2p_ring_allgather_traffic(tree, 188, n * 188)
    mc = cm.mcast_allgather_traffic(tree, 188, n * 188)
    ringb = cm.p2p_ring_pipeline_bcast_traffic(tree, 188, n)
    kno = cm.p2p_knomial_bcast_traffic(tree, 188, n)
    mcb = cm.mcast_bcast_traffic(tree, 188, n)
    rows.append(("fig12.allgather_reduction_x", round(ring / mc, 2),
                 "paper: 1.5-2x"))
    rows.append(("fig12.bcast_reduction_x", round(ringb / mcb, 2),
                 "vs pipelined-ring P2P; paper: 1.5x"))
    rows.append(("fig12.bcast_vs_knomial_x", round(kno / mcb, 2),
                 "vs locality-naive k-nomial (worse baseline)"))
    assert 1.5 <= ring / mc <= 2.2
    assert 1.3 <= ringb / mcb <= 2.5
    return rows


def table1_datapath():
    """Table I: single-thread DPA receive datapath metrics."""
    rows = []
    for t in ("UD", "UC"):
        r = dpa.TABLE1[t]
        rows.append((f"table1.{t}.tput_gibs", r["tput_gib"], ""))
        rows.append((f"table1.{t}.cycles_per_cqe", r["cycles_per_cqe"],
                     f"ipc={r['ipc']}"))
    assert dpa.TABLE1["UC"]["tput_gib"] / dpa.TABLE1["UD"]["tput_gib"] > 2
    return rows


def fig13_14_thread_scaling():
    """Figs 13/14: receive throughput vs DPA threads (8 MiB buffer, 4 KiB)."""
    rows = []
    for t in ("UD", "UC"):
        for n in (1, 2, 4, 8, 16):
            tput = dpa.sustained_tput(dpa.DpaConfig(t, n)) / GIB
            rows.append((f"fig13.{t}.{n}threads_gibs", round(tput, 2), ""))
        sat = dpa.threads_to_saturate(t)
        rows.append((f"fig14.{t}.threads_to_linerate", sat,
                     "paper: UC~4, UD 8-16"))
    assert dpa.threads_to_saturate("UC") <= 4
    assert 8 <= dpa.threads_to_saturate("UD") <= 16
    return rows


def fig15_chunk_sizes():
    """Fig 15: UC multi-packet chunks saturate with fewer threads."""
    rows = []
    for chunk in (4096, 8192, 16384, 32768):
        n = next(
            t for t in range(1, 257)
            if dpa.sustained_tput(dpa.DpaConfig("UC", t, chunk))
            >= 0.99 * dpa.LINK_200G_BYTES
        )
        rows.append((f"fig15.UC.{chunk}B.threads_to_linerate", n, ""))
    return rows


def fig16_tbit():
    """Fig 16: 64 B chunks — sustained chunk rate vs the 1.6 Tbit/s arrival."""
    need = dpa.link_chunk_arrival_rate(dpa.LINK_1600G_BYTES)
    rows = [("fig16.required_Mchunks_s", round(need / 1e6, 1), "1.6T, 4KiB MTU")]
    for n in (16, 64, 128):
        r = dpa.sustained_chunk_rate(
            dpa.DpaConfig("UD", n, 64, dpa.LINK_1600G_BYTES)
        )
        rows.append((f"fig16.UD.{n}threads_Mchunks_s", round(r / 1e6, 1),
                     "sustains 1.6T" if r >= need else "below"))
    assert dpa.tbit_feasible("UD", 128)
    return rows


def appendix_b_speedup():
    """Appendix B: S = 2 - 2/P for concurrent {AG, RS}."""
    rows = []
    for p in (2, 16, 256, 1024):
        s = cm.concurrent_ag_rs_speedup(p)
        t_rr = cm.concurrent_completion_time(1 << 20, p, 25e9, "ring_ring")
        t_mi = cm.concurrent_completion_time(1 << 20, p, 25e9, "mc_inc")
        rows.append((f"appB.S(P={p})", round(s, 4),
                     f"sim ratio {t_rr/t_mi:.4f}"))
        assert abs(t_rr / t_mi - s) < 1e-9
    return rows


def fabric_sweep(hosts_list=(128, 512, 1024)):
    """Fig. 2's P2P-vs-multicast port-counter curve on the ROUTED engine:
    all P ranks placed on a k=32 fat-tree, every transfer a routed/tree flow,
    so ONE engine run per schedule yields both the completion time and the
    per-link switch-port bytes (no static counting pass). Asserts byte
    conservation against the tree/route edge counts and the paper's Insight-1
    reduction: multicast Allgather <= 0.55x the P2P ring bytes at >=512
    hosts (~2x, Fig. 12)."""
    k = 32
    shard = 64 << 10                       # 64 KiB per rank (Fig. 12 counter run)
    fab = FabricParams(p_drop=0.0, jitter=0.0)
    wk = WorkerParams(n_recv_workers=16)
    rows = []
    for p in hosts_list:
        topo = FatTree(k=k, n_hosts=p, b_host=fab.b_link)
        hosts = list(range(p))
        ag = simulate_allgather(p, shard, fab, wk, np.random.default_rng(0),
                                n_chains=p, topology=topo)
        mc_bytes = sum(ag.link_bytes.values())
        # conservation: each tree flow serves its bytes on every tree edge
        mc_expect = shard * sum(
            len(topo.multicast_tree(h, hosts)) for h in hosts)
        assert abs(mc_bytes - mc_expect) <= 1e-6 * mc_expect, (mc_bytes, mc_expect)

        t_ring, ring_lb = cm.routed_ring_allgather(topo, p, p * shard, fab)
        ring_bytes = sum(ring_lb.values())
        ring_expect = (p - 1) * shard * sum(
            len(topo.route(hosts[i], hosts[(i + 1) % p])) for i in range(p))
        assert abs(ring_bytes - ring_expect) <= 1e-6 * ring_expect, (
            ring_bytes, ring_expect)

        red = ring_bytes / mc_bytes
        rows.append((f"fabric.P{p}.ring_port_bytes", int(ring_bytes),
                     f"t={t_ring*1e3:.2f}ms"))
        rows.append((f"fabric.P{p}.mcast_port_bytes", int(mc_bytes),
                     f"t={ag.time*1e3:.2f}ms x{red:.2f} less traffic"))
        # Insight 1 at scale: >= ~2x reduction measured at the switch ports,
        # from the same runs that produced the times
        if p >= 512:
            assert mc_bytes <= 0.55 * ring_bytes, (p, mc_bytes / ring_bytes)
        else:
            assert mc_bytes < ring_bytes
        # both schedules are receive-bound (paper: "such alignment is
        # expected") — but the ring pays P-1 activation latencies while the
        # multicast pays constant sync, so it must not be slower
        t_bound = (p - 1) * shard / fab.b_link
        assert t_bound * 0.95 <= ag.time <= t_ring, (t_bound, ag.time, t_ring)
    return rows


def fabric_sweep_smoke():
    """CI-sized fabric_sweep (<~10 s): same asserts, capped at 512 hosts."""
    return fabric_sweep(hosts_list=(128, 512))


def multi_job_contention():
    """Two FSDP jobs on disjoint hosts of one fat-tree: full bisection
    isolates them (slowdown 1.0x); oversubscribing the switch tiers makes
    their multicast trees collide on shared agg/core links."""
    rows = []
    jobs = {"A": list(range(0, 32, 2)), "B": list(range(1, 32, 2))}
    slowdowns = {}
    for o in (1.0, 2.0, 4.0):
        topo = FatTree(k=8, n_hosts=32, oversubscription=o)
        r = simulate_multi_job(topo, jobs, layer_bytes=128e6, n_layers=3,
                               policy="mcast")
        s = max(r.slowdown.values())
        slowdowns[o] = s
        rows.append((f"multijob.oversub{o:g}.slowdown_x", round(s, 3),
                     f"solo={min(r.solo_time.values())*1e3:.2f}ms "
                     f"core={r.core_bytes/1e9:.2f}GB"))
    assert slowdowns[1.0] < 1.01, slowdowns       # full bisection: isolated
    assert slowdowns[4.0] > 1.3, slowdowns        # oversubscribed: interference
    assert slowdowns[1.0] <= slowdowns[2.0] <= slowdowns[4.0], slowdowns
    return rows


def protocol_loss_sweep(p_list=(16, 64, 256, 512), *, n_bytes=1 << 20,
                        link_loss=1e-3, seeds=(0, 1, 2), crossover_p=64,
                        loss_grid=(1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2,
                                   1e-1, 2e-1, 3e-1)):
    """Packet-level reliability headline (§III): at a fixed 0.1% per-link
    loss, NACK-aggregation + multicast-retransmission recovery time grows
    no faster than O(log p) — the fat-tree depth is constant, the root
    serves ONE aggregated NACK per round, and the retransmit union
    saturates. Also locates the loss rate at which reliable-unicast ring
    broadcast overtakes multicast+recovery (it must sit well above the
    paper's operating point), and reports the Gilbert-Elliott bursty-loss
    contrast at equal mean rate."""
    from repro.core.packet import GilbertElliottLoss

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=16)
    rows = []

    # -- part A: recovery-time growth in p at fixed per-link loss
    rec = {}
    for p in p_list:
        k = 32 if p > 128 else 16
        per = []
        for s in seeds:
            topo = FatTree(k=k, n_hosts=p, b_host=fab.b_link)
            r = simulate_broadcast(p, n_bytes, fab, wk,
                                   np.random.default_rng(s), topology=topo,
                                   fidelity="packet", loss=link_loss)
            assert r.completed, (p, s)
            assert r.bytes_fast + r.bytes_recovery == r.bytes_total
            per.append(r.phases.reliability)
        rec[p] = sum(per) / len(per)
        rows.append((f"proto.P{p}.recovery_us", round(rec[p] * 1e6, 1),
                     f"{link_loss:g} per-link loss, mean of {len(seeds)} seeds"))
    p0, p1 = min(p_list), max(p_list)
    growth = rec[p1] / rec[p0]
    log_bound = math.log2(p1) / math.log2(p0)
    rows.append(("proto.recovery_growth_x", round(growth, 3),
                 f"P{p0}->P{p1}; O(log p) bound {log_bound:.2f}"))
    # constant-time claim: growth bounded by the log-p envelope (slack for
    # sampling noise); a linear-in-p protocol would show ~p1/p0 = 32x here
    assert growth <= log_bound * 1.5, (growth, log_bound)

    # NACK-aggregation ablation (same seed, same loss draws): without
    # in-tree ORs the root pool serves one NACK per nacker instead of one
    # aggregate, so recovery can only get slower
    k1 = 32 if p1 > 128 else 16
    runs = {}
    for agg in (True, False):
        topo = FatTree(k=k1, n_hosts=p1, b_host=fab.b_link)
        runs[agg] = simulate_broadcast(
            p1, n_bytes, fab, wk, np.random.default_rng(seeds[0]),
            topology=topo, fidelity="packet", loss=link_loss,
            aggregate_nacks=agg)
    rows.append((f"proto.P{p1}.noagg_recovery_us",
                 round(runs[False].phases.reliability * 1e6, 1),
                 f"vs {runs[True].phases.reliability*1e6:.1f}us aggregated"))
    assert (runs[False].phases.reliability
            >= runs[True].phases.reliability - 1e-12)
    # DPA NACK budget context: even WITHOUT aggregation a 16-thread pool
    # could absorb every leaf's NACK each round at the largest scale here
    nack_budget = dpa.nack_rate(dpa.DpaConfig("UD", 16))
    rows.append(("proto.dpa_nack_rate_msgs_per_s", int(nack_budget),
                 f"16 UD threads; P{p1} worst case needs {p1 - 1}/round"))
    assert nack_budget > p1 - 1

    # -- part B: multicast-vs-unicast crossover loss rate
    p = crossover_p
    t_mc, t_ring = [], []
    for q in loss_grid:
        per = [simulate_broadcast(p, n_bytes, fab, wk,
                                  np.random.default_rng(s),
                                  fidelity="packet", loss=q).time
               for s in seeds]
        t_mc.append(sum(per) / len(per))
        t_ring.append(protocol.analytic_ring_pipeline_bcast_time(
            p, n_bytes, fab.b_link, fab.latency, loss_rate=q))
    crossover = None
    for i, q in enumerate(loss_grid):
        rows.append((f"proto.loss{q:g}.mcast_vs_ring_x",
                     round(t_mc[i] / t_ring[i], 3),
                     f"mcast={t_mc[i]*1e6:.0f}us ring={t_ring[i]*1e6:.0f}us"))
        if crossover is None and t_mc[i] > t_ring[i]:
            crossover = (math.sqrt(loss_grid[i - 1] * q) if i else q)
    rows.append(("proto.crossover_loss",
                 crossover if crossover is not None else float("inf"),
                 f"P={p}, {n_bytes>>10} KiB: unicast ring wins above this"))
    # multicast+recovery must still win at the paper's 0.1% operating point
    assert crossover is None or crossover > 1e-3, crossover

    # -- part C: bursty (Gilbert-Elliott) vs i.i.d. loss at equal mean rate
    rate, burst = 1e-2, 16.0
    ge = GilbertElliottLoss.from_rate(rate, mean_burst=burst)
    r_ge = simulate_broadcast(p, n_bytes, fab, wk, np.random.default_rng(0),
                              fidelity="packet", loss=ge)
    r_iid = simulate_broadcast(p, n_bytes, fab, wk, np.random.default_rng(0),
                               fidelity="packet", loss=rate)
    assert r_ge.completed and r_iid.completed
    rows.append(("proto.ge_vs_iid_recovery_x",
                 round(r_ge.phases.reliability
                       / max(r_iid.phases.reliability, 1e-12), 3),
                 f"burst={burst:g} pkts at rate {rate:g}"))
    return rows


def protocol_loss_sweep_smoke():
    """CI-sized protocol_loss_sweep (seconds): same asserts, capped at 128
    hosts / 256 KiB and a coarser crossover grid."""
    return protocol_loss_sweep(
        p_list=(16, 64, 128), n_bytes=1 << 18, seeds=(0, 1),
        loss_grid=(1e-3, 1e-2, 3e-2, 1e-1, 3e-1))


def packet_scale_sweep(grid=((512, 1 << 26), (2048, 1 << 26), (10000, GIB)),
                       ref_grid=((512, 1 << 26), (2048, 1 << 26)),
                       big=(10000, GIB), ag_point=(512, 1 << 20, 4),
                       ag_dense=(128, 16 << 20, 4),
                       min_big_speedup=20.0, min_dense_speedup=1.0):
    """Simulator-throughput benchmark: wall-clock of the packet-fidelity
    engine itself vs host count, vectorized batch engine (default) against
    the per-leaf reference oracle. Lossless jitter-0 fabric with an 8-thread
    pool (pool rate > wire rate, so no staging RNR) — both engines replay
    the identical protocol and must return identical results; the lossy /
    RNR / multi-chain grid is pinned bit-exact by
    tests/test_packet_vectorized.py. Wall-clock rows (``*_wall_s`` /
    ``*_speedup``) are machine-dependent: benchmarks/run.py carries them in
    BENCH_smoke.json's ``wall_clock`` section and scripts/bench_gate.py
    reports their drift informationally — they are never gated."""
    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    rows = []
    vec_wall = {}

    def timed(fn, *args, **kw):
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        return r, time.perf_counter() - t0

    for p, n in grid:
        r, w = timed(simulate_broadcast, p, n, fab, wk,
                     np.random.default_rng(0), fidelity="packet",
                     engine="vectorized")
        assert r.completed, (p, n)
        vec_wall[p] = w
        rows.append((f"pscale.P{p}.vec_wall_s", round(w, 4),
                     f"bcast {n >> 20} MiB, vectorized engine"))
    # reference oracle at the small/mid points: identical results, and the
    # measured per-leaf wall-clock the batch engine is judged against
    for p, n in ref_grid:
        rr, w = timed(simulate_broadcast, p, n, fab, wk,
                      np.random.default_rng(0), fidelity="packet",
                      engine="reference")
        rv = simulate_broadcast(p, n, fab, wk, np.random.default_rng(0),
                                fidelity="packet", engine="vectorized")
        assert (rr.time, rr.completed, rr.bytes_total, rr.bytes_recovery) \
            == (rv.time, rv.completed, rv.bytes_total, rv.bytes_recovery)
        rows.append((f"pscale.P{p}.ref_wall_s", round(w, 4),
                     f"bcast {n >> 20} MiB, per-leaf reference"))
        rows.append((f"pscale.P{p}.ref_vs_vec_speedup",
                     round(w / max(vec_wall[p], 1e-9), 1),
                     "reference / vectorized wall-clock"))
    # the 10k-host headline: full reference run, recorded + floor-asserted
    if big is not None:
        p, n = big
        rr, w = timed(simulate_broadcast, p, n, fab, wk,
                      np.random.default_rng(0), fidelity="packet",
                      engine="reference")
        assert rr.completed, (p, n)
        speedup = w / max(vec_wall[p], 1e-9)
        rows.append((f"pscale.P{p}.ref_wall_s", round(w, 4),
                     f"bcast {n >> 20} MiB, per-leaf reference"))
        rows.append((f"pscale.P{p}.ref_vs_vec_speedup", round(speedup, 1),
                     f"floor {min_big_speedup:g}x"))
        assert speedup >= min_big_speedup, (speedup, w, vec_wall[p])
    # allgather point: same contract on the multi-chain path
    p, n, m = ag_point
    ra, wv = timed(simulate_allgather, p, n, fab, wk,
                   np.random.default_rng(0), m, fidelity="packet",
                   engine="vectorized")
    rf, wr = timed(simulate_allgather, p, n, fab, wk,
                   np.random.default_rng(0), m, fidelity="packet",
                   engine="reference")
    assert ra.completed and (ra.time, ra.bytes_total, ra.bytes_recovery) \
        == (rf.time, rf.bytes_total, rf.bytes_recovery)
    rows.append((f"pscale.AG.P{p}.vec_wall_s", round(wv, 4),
                 f"allgather {n >> 20} MiB x{m} chains, vectorized"))
    rows.append((f"pscale.AG.P{p}.ref_wall_s", round(wr, 4),
                 f"allgather {n >> 20} MiB x{m} chains, reference"))
    rows.append((f"pscale.AG.P{p}.ref_vs_vec_speedup",
                 round(wr / max(wv, 1e-9), 1),
                 "reference / vectorized wall-clock"))
    # dense big-row allgather (DESIGN §9/§13): few hosts, >= 16 MiB merged
    # rows — the regime the residue-class-parallel pool scan closed. The
    # engine="auto" fallback is retired, so this point carries a hard
    # vectorized >= reference floor (the closure must not silently reopen).
    if ag_dense is not None:
        p, n, m = ag_dense
        ra, wv = timed(simulate_allgather, p, n, fab, wk,
                       np.random.default_rng(0), m, fidelity="packet",
                       engine="vectorized")
        rf, wr = timed(simulate_allgather, p, n, fab, wk,
                       np.random.default_rng(0), m, fidelity="packet",
                       engine="reference")
        assert ra.completed and (ra.time, ra.bytes_total, ra.bytes_recovery) \
            == (rf.time, rf.bytes_total, rf.bytes_recovery)
        dense = wr / max(wv, 1e-9)
        rows.append((f"pscale.AGdense.P{p}.vec_wall_s", round(wv, 4),
                     f"allgather {n >> 20} MiB x{m} chains, vectorized"))
        rows.append((f"pscale.AGdense.P{p}.ref_wall_s", round(wr, 4),
                     f"allgather {n >> 20} MiB x{m} chains, reference"))
        rows.append((f"pscale.AGdense.P{p}.ref_vs_vec_speedup",
                     round(dense, 2), f"floor {min_dense_speedup:g}x"))
        assert dense >= min_dense_speedup, (dense, wr, wv)
    return rows


def packet_scale_sweep_smoke():
    """CI-sized packet_scale_sweep: keeps the acceptance-gating 10k-host /
    1 GiB reference-vs-vectorized speedup (the one long row, ~2 min of
    reference wall-clock) but trims the mid-scale reference points."""
    return packet_scale_sweep(grid=((512, 1 << 26), (10000, GIB)),
                              ref_grid=((512, 1 << 26),),
                              ag_point=(256, 1 << 20, 4))


def dpa_scaling_sweep(thread_grid=(1, 2, 4, 8, 16)):
    """Figs 13/14/16 + §VII-d on the EVENT-level DPA progress engine
    (core/dpa_engine.py): thread-scaling and saturation measured by driving
    the simulator with line-rate traces — multithreading hides the
    stalled-on-memory cycles mechanistically instead of applying the
    analytic T^e envelope — with core/dpa.py retained as the cross-check
    oracle (full-core capacity and the Fig-16 margin must land within 10%).
    Also pins the §VII-d offload economics: one DPA core vs one host core
    (Fig 5), the FSDP freed-host-cycles benefit, and the cycle-stealing
    cost of running the recovery protocol on the receive contexts."""
    from repro.core import dpa_engine as de
    from repro.core.engine import simulate_fsdp_step
    from repro.core.simulator import simulate_broadcast as sim_bcast

    rows = []
    # -- Figs 13/14: receive throughput vs threads, saturation thread counts
    for t in ("UD", "UC"):
        for n in thread_grid:
            ev = de.sustained_tput_event(de.EventDpaParams.from_table1(t, n))
            rows.append((f"dpaev.fig13.{t}.{n}threads_gibs",
                         round(ev / GIB, 2),
                         f"analytic {dpa.sustained_tput(dpa.DpaConfig(t, n))/GIB:.2f}"))
        sat_ev = de.threads_to_saturate_event(t)
        sat_an = dpa.threads_to_saturate(t)
        rows.append((f"dpaev.fig14.{t}.sat_vs_analytic_x",
                     round(sat_ev / sat_an, 3),
                     f"event saturates 200G at {sat_ev} threads, "
                     f"analytic at {sat_an}"))
    assert de.threads_to_saturate_event("UC") <= 4          # paper: ~4
    assert 8 <= de.threads_to_saturate_event("UD") <= 16    # paper: 8-16
    # full-core capacity anchors: the event engine must land on the oracle
    for t in ("UD", "UC"):
        ev = de.pool_tput_event(de.EventDpaParams.from_table1(t, 16))
        an = dpa.pool_tput(dpa.DpaConfig(t, 16))
        rows.append((f"dpaev.{t}.core16_vs_oracle_x", round(ev / an, 3),
                     f"event {ev/GIB:.2f} vs pool_tput {an/GIB:.2f} GiB/s"))
        assert abs(ev / an - 1.0) < 0.10, (t, ev, an)

    # -- Fig 16: 64 B chunks, 128 threads vs the 1.6 Tbit/s arrival rate
    need = dpa.link_chunk_arrival_rate(dpa.LINK_1600G_BYTES)
    rate = de.sustained_chunk_rate_event(
        de.EventDpaParams.from_table1("UD", 128), need, chunk_bytes=64)
    an_rate = dpa.sustained_chunk_rate(
        dpa.DpaConfig("UD", 128, 64, dpa.LINK_1600G_BYTES))
    rows.append(("dpaev.fig16.UD128_vs_required_x", round(rate / need, 3),
                 f"{rate/1e6:.1f} of {need/1e6:.1f} Mchunks/s"))
    assert de.tbit_feasible_event("UD", 128)
    assert not de.tbit_feasible_event("UD", 8)
    assert abs(rate / an_rate - 1.0) < 0.10, (rate, an_rate)  # 10% of oracle

    # -- Fig 5 / §VII-d: one multithreaded DPA core vs one host CPU core
    dpa_core = de.sustained_tput_event(de.EventDpaParams.from_table1("UD", 16))
    host_core = de.pool_tput_event(de.EventDpaParams.host_cpu(1))
    rows.append(("dpaev.fig5.dpa_core_vs_host_core_x",
                 round(dpa_core / host_core, 3),
                 f"host core {host_core/GIB:.1f} GiB/s cannot hold 200G"))
    assert dpa_core / host_core > 1.2 and host_core < dpa.LINK_200G_BYTES

    # -- freed-host-cycles benefit in the FSDP bubble accounting
    kw = dict(n_layers=4, layer_bytes=64e6, p=16, policy="split")
    d = simulate_fsdp_step(**kw)
    h = simulate_fsdp_step(**kw, progress_engine="host", host_cores=2)
    rows.append(("dpaev.fsdp.host_vs_dpa_step_x",
                 round(h.step_time / d.step_time, 3),
                 f"host bubbles {h.bubble_fraction:.3f} vs DPA "
                 f"{d.bubble_fraction:.3f}"))
    assert h.step_time > d.step_time
    assert h.bubble_fraction > d.bubble_fraction

    # -- cycle stealing: the same lossy Broadcast through the scalar pool
    # and through the event engine (NACK + retransmit posting contend with
    # the receive datapath) — the event fidelity can only be slower
    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=16)
    scl = sim_bcast(16, 1 << 20, fab, wk, np.random.default_rng(0),
                    fidelity="packet", loss=1e-3)
    evt = sim_bcast(16, 1 << 20, fab, wk, np.random.default_rng(0),
                    fidelity="packet", loss=1e-3, dpa_fidelity="event")
    rows.append(("dpaev.P16.event_vs_scalar_x",
                 round(evt.time / scl.time, 4),
                 f"event {evt.time*1e6:.1f}us scalar {scl.time*1e6:.1f}us"))
    assert evt.completed and evt.time >= scl.time - 1e-12
    return rows


def dpa_scaling_smoke():
    """CI-sized dpa_scaling_sweep: the full sweep is already seconds-scale
    (event traces are tens of thousands of CQEs), so smoke == full grid."""
    return dpa_scaling_sweep()


def schedule_ir_sweep():
    """Collective Schedule IR smoke: Allreduce lowered from ONE schedule
    graph, comparing the RS∘multicast-AG composition (the paper's AG as the
    second phase) against the classical ring allreduce — wall time on the
    abstract full-duplex NIC and switch-port bytes on a routed fat-tree
    (Insight 1 transplanted to allreduce) — plus the per-fabric chain
    autotune. All rows are deterministic model ratios (jitter 0, loss 0)."""
    from repro.core import sched_ir

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    n = 1 << 22                                   # 4 MiB per-rank buffer
    rows = []
    for p in (16, 64):
        mc = sched_ir.execute(sched_ir.build_allreduce(p, n, m=p), fab, wk,
                              np.random.default_rng(0))
        ring = sched_ir.execute(sched_ir.build_allreduce(p, n), fab, wk,
                                np.random.default_rng(0))
        rows.append((f"schedir.P{p}.allreduce_ring_vs_mcast_time_x",
                     round(ring.time / mc.time, 4),
                     f"ring={ring.time*1e6:.1f}us mcast={mc.time*1e6:.1f}us"))
        topo = FatTree(k=8, n_hosts=p, b_host=fab.b_link)
        mc_r = sched_ir.execute(sched_ir.build_allreduce(p, n, m=p), fab, wk,
                                np.random.default_rng(0), topology=topo)
        mc_bytes = sum(mc_r.link_bytes.values())
        topo = FatTree(k=8, n_hosts=p, b_host=fab.b_link)
        ring_r = sched_ir.execute(sched_ir.build_allreduce(p, n), fab, wk,
                                  np.random.default_rng(0), topology=topo)
        ring_bytes = sum(ring_r.link_bytes.values())
        # Insight 1 on the composed collective: switch replication must cut
        # the fabric bytes of the AG phase
        assert mc_bytes < ring_bytes, (p, mc_bytes, ring_bytes)
        rows.append((f"schedir.P{p}.allreduce_mcast_vs_ring_fabric_bytes_x",
                     round(mc_bytes / ring_bytes, 4),
                     f"mcast={mc_bytes/GIB:.3f}GiB ring={ring_bytes/GIB:.3f}GiB"))
    best, times = sched_ir.autotune_chains(
        sched_ir.build_allgather, p=64, n_bytes=1 << 18, fabric=fab,
        workers=wk)
    assert best == 64, times                     # flat fabric: full parallelism
    rows.append(("schedir.autotune_flat_best_m", best,
                 f"candidates={sorted(times)}"))
    thin = FatTree(k=8, n_hosts=16, b_host=fab.b_link, oversubscription=4.0)
    best_thin, _ = sched_ir.autotune_chains(
        sched_ir.build_allgather, thin, p=16, n_bytes=1 << 18, fabric=fab,
        workers=wk)
    rows.append(("schedir.autotune_oversub4_best_m", best_thin,
                 "16 hosts, 4x oversubscribed fat-tree"))
    return rows


def search_sweep():
    """Derived schedules (core/sched_search.py): on the oversubscribed
    fat-tree AND the torus the searched allreduce must beat the best
    hand-written builder at fluid fidelity (strictly on at least one),
    validate at packet fidelity under loss, and report its lower-bound
    certificate — all inside the smoke wall budget. The eval cache is the
    persistent one ($REPRO_EVAL_CACHE when set — nightly CI carries it
    across runs as an artifact); a warmed re-search of both fabrics then
    self-verifies the cache contract: >= 3x faster than the cold fluid
    sweep, identical winners."""
    from repro.core import sched_search

    cache = sched_search.EvalCache.persistent()
    p, n = 16, 16 << 20
    scenarios = [
        ("fattree_os4", FatTree(k=8, n_hosts=p, oversubscription=4.0)),
        ("torus4x4", Torus2D(4, 4)),
    ]
    rows = []
    ratios = []
    t0 = time.perf_counter()
    for label, topo in scenarios:
        r = sched_search.search("allreduce", p, n, topology=topo,
                                loss=1e-3, cache=cache)
        assert r.packet_validated, f"{label}: winner failed packet validation"
        assert r.certificate.ratio >= 1.0 - 1e-9, \
            f"{label}: winner beat its own admissible bound"
        ratio = r.searched_vs_best_builder
        ratios.append(ratio)
        rows.append((f"search.{label}.searched_vs_best_builder_x",
                     round(ratio, 4),
                     f"{r.winner.name} vs {r.best_builder.name}"))
        rows.append((f"search.{label}.bound_cert_x",
                     round(r.certificate.ratio, 4),
                     f"winner/bound, binding={r.certificate.binding}"))
        rows.append((f"search.{label}.fabric_bytes_x",
                     round(r.winner_fabric_bytes
                           / r.best_builder_fabric_bytes, 4),
                     f"routed bytes, winner={r.winner_fabric_bytes/GIB:.3f}"
                     f"GiB"))
    wall = time.perf_counter() - t0
    assert all(x <= 1.0 + 1e-9 for x in ratios), ratios
    assert min(ratios) < 1.0, f"no strict win over builders: {ratios}"
    assert wall < 30.0, f"search sweep blew the smoke budget: {wall:.1f}s"
    rows.append(("search.allreduce_p16_wall_s", round(wall, 3),
                 "both fabrics, shared eval cache"))
    # warm-cache contract: a cold fluid sweep (fresh cache, no packet
    # validation so the comparison isolates the searcher) vs the same sweep
    # served from the now-populated cache — the memoization must buy >= 3x
    # and change nothing about the winners
    t_cold = time.perf_counter()
    for label, topo in scenarios:
        sched_search.search("allreduce", p, n, topology=topo,
                            validate_packet=False,
                            cache=sched_search.EvalCache())
    wall_cold = time.perf_counter() - t_cold
    t_warm = time.perf_counter()
    warm_hits0 = cache.hits
    for label, topo in scenarios:
        rw = sched_search.search("allreduce", p, n, topology=topo,
                                 validate_packet=False, cache=cache)
        assert rw.cache_hits == rw.evaluations, (label, rw.cache_hits)
    wall_warm = time.perf_counter() - t_warm
    warm_x = wall_cold / max(wall_warm, 1e-9)
    rows.append(("search.warm_cache_speedup", round(warm_x, 1),
                 f"cold {wall_cold:.2f}s vs warm {wall_warm:.3f}s, "
                 f"{cache.hits - warm_hits0} hits"))
    assert warm_x >= 3.0, (warm_x, wall_cold, wall_warm)
    cache.save()
    # informational (ungated: neither a ratio nor a wall row) — the nightly
    # CI job lifts this into $GITHUB_STEP_SUMMARY next to the uploaded
    # persistent-cache artifact
    total_evals = cache.hits + cache.misses
    rows.append(("search.eval_cache_hit_rate",
                 round(cache.hits / max(total_evals, 1), 4),
                 f"{cache.hits}/{total_evals} evals served from cache"
                 + (f"; persisted to {cache.path}" if cache.path else "")))
    return rows


def hier_fabric_sweep():
    """Tiered island fabrics (core/topology.IslandFatTree): the searched
    mixed-transport allgather must strictly beat BOTH the flat multicast
    builder and the pure island-ring builder at P in {64, 256}, carry a
    BoundCertificate ratio >= 1 from the tiered analytic bounds, and shed
    switched-tier fabric bytes onto the island tier (FlexLink-style,
    arXiv:2510.15882). All gated rows are deterministic model ratios."""
    from repro.core import sched_ir, sched_search
    from repro.core.topology import IslandFatTree

    fab = FabricParams(jitter=0.0)
    wk = WorkerParams(n_recv_workers=8)
    n = 1 << 20                                   # 1 MiB per-rank buffer
    cache = sched_search.EvalCache()
    rows = []
    t0 = time.perf_counter()
    for k, p in ((8, 64), (16, 256)):
        topo = IslandFatTree(k, p, island_size=8)
        hosts = list(range(p))
        r = sched_search.search("allgather", p, n, topology=topo,
                                hosts=hosts, cache=cache)
        assert r.winner.sched.kind == "hier_allgather", r.winner.name
        assert r.packet_validated, f"P={p}: winner failed packet validation"
        assert r.certificate.ratio >= 1.0 - 1e-9, \
            f"P={p}: winner beat its own admissible tiered bound"
        flat_t = min(row.time for row in r.table
                     if row.name.startswith("builder:mcast")
                     and row.time is not None)
        ring_t = next(row.time for row in r.table
                      if row.name == "builder:ring")
        assert r.winner_time < flat_t and r.winner_time < ring_t, \
            (p, r.winner_time, flat_t, ring_t)
        rows.append((f"hier.P{p}.searched_vs_flat_mcast_x",
                     round(r.winner_time / flat_t, 4),
                     f"{r.winner.name} vs best flat multicast"))
        rows.append((f"hier.P{p}.searched_vs_island_ring_x",
                     round(r.winner_time / ring_t, 4),
                     f"{r.winner.name} vs routed unicast ring"))
        rows.append((f"hier.P{p}.bound_cert_x",
                     round(r.certificate.ratio, 4),
                     f"winner/bound, binding={r.certificate.binding}"))
        # per-tier fabric bytes: the winner's switched-tier relief is the
        # headline — total routed bytes barely move (the redistribution
        # still touches every rank), they just ride the island cables
        topo.reset()
        win = sched_ir.execute(r.winner.sched, fab, wk,
                               np.random.default_rng(0), topology=topo,
                               hosts=hosts)
        win_split = topo.tier_split(win.link_bytes)
        topo.reset()
        flat = sched_ir.execute(sched_ir.build_allgather(p, n, p), fab, wk,
                                np.random.default_rng(0), topology=topo,
                                hosts=hosts)
        flat_split = topo.tier_split(flat.link_bytes)
        assert win_split["switched"] < flat_split["switched"], (p, win_split)
        assert flat_split.get("island", 0.0) == 0.0
        rows.append((f"hier.P{p}.switched_bytes_vs_flat_x",
                     round(win_split["switched"] / flat_split["switched"], 4),
                     f"winner switched={win_split['switched']/GIB:.3f}GiB "
                     f"island={win_split.get('island', 0.0)/GIB:.3f}GiB"))
    wall = time.perf_counter() - t0
    rows.append(("hier.allgather_search_wall_s", round(wall, 3),
                 "P=64+256 island fabrics, shared eval cache"))
    return rows


def fsdp_contention_sweep():
    """Abstract's opening claim: interleaved AG/RS contend for injection
    bandwidth; the multicast schedule and the Insight-2 direction split cut
    the resulting pipeline bubbles (core/engine.py FSDP timeline)."""
    data = sweep_fsdp_contention(ps=(16, 64), layer_bytes=(64e6, 256e6),
                                 n_layers=8)
    rows = []
    bubbles = {}
    for r in data:
        key = (r["p"], r["layer_bytes"])
        bubbles.setdefault(key, {})[r["policy"]] = r["bubble_fraction"]
        rows.append((
            f"fsdp.P{r['p']}.{int(r['layer_bytes']/1e6)}MBlayer."
            f"{r['policy']}.bubble_frac",
            round(r["bubble_fraction"], 4),
            f"step={r['step_time']*1e3:.1f}ms "
            f"util={max(r['link_utilization'].values()):.2f}",
        ))
    for key, b in bubbles.items():
        assert b["split"] < b["naive"], (key, b)   # strictly lower bubbles
    return rows


def training_run_sweep():
    """GPT-scale compute+comm co-sim (core/train_sim.py): the registry
    span smollm-135m -> granite-34b end-to-end at three host scales, the
    split-vs-naive MFU win on an oversubscribed fabric, the loss
    degradation curve and the fidelity ordering. All gated rows are
    deterministic model ratios (machine-independent)."""
    from repro.configs.registry import training_sweep_archs
    from repro.core.train_sim import simulate_training_run

    fab = FabricParams(jitter=0.0)
    rows = []
    t0 = time.perf_counter()

    # ---- host-count scaling: every sweep model x {16, 64, 256} hosts
    for arch in training_sweep_archs():
        steps = {}
        for n_hosts in (16, 64, 256):
            r = simulate_training_run(arch, n_hosts=n_hosts, policy="split",
                                      fabric=fab)
            assert 0.0 < r.mfu <= 1.0, (arch, n_hosts, r.mfu)
            steps[n_hosts] = r.step_time
        assert steps[16] > steps[64] > steps[256], (arch, steps)
        rows.append((f"train.{arch}.scale16to256_x",
                     round(steps[16] / steps[256], 4),
                     f"step 16h={steps[16]:.3f}s 256h={steps[256]:.4f}s"))

    # ---- the split-policy MFU win at oversubscription 4 (Insight 2 on
    # the fabric: AG_mc down + RS_inc up vs the self-colliding ring)
    pols = {}
    for pol in ("naive", "split"):
        pols[pol] = simulate_training_run(
            "smollm-135m", n_hosts=16, policy=pol, fabric=fab,
            topology=FatTree(k=8, n_hosts=16, oversubscription=4.0))
    assert pols["split"].mfu > pols["naive"].mfu, pols
    assert pols["split"].step_time < pols["naive"].step_time
    rows.append(("train.smollm-135m.P16.split_vs_naive_mfu_x",
                 round(pols["split"].mfu / pols["naive"].mfu, 4),
                 f"split mfu={pols['split'].mfu:.3f} "
                 f"naive={pols['naive'].mfu:.3f} (oversub 4 fat-tree)"))
    for pol, r in pols.items():
        rows.append((f"train.smollm-135m.P16.{pol}.bubble_frac",
                     round(r.bubble_fraction, 4),
                     f"step={r.step_time*1e3:.1f}ms mfu={r.mfu:.3f}"))
    assert pols["split"].bubble_fraction < pols["naive"].bubble_fraction

    # ---- loss degradation + fidelity ordering (abstract fabric)
    fl = simulate_training_run("smollm-135m", n_hosts=16, policy="split",
                               fabric=fab)
    an = simulate_training_run("smollm-135m", n_hosts=16, policy="split",
                               fabric=fab, fidelity="analytic")
    pk = {}
    for q in (0.001, 0.01):
        pk[q] = simulate_training_run(
            "smollm-135m", n_hosts=16, policy="split", fabric=fab,
            fidelity="packet", loss=q, rng=np.random.default_rng(0))
    assert an.step_time <= fl.step_time + 1e-12
    assert fl.step_time <= pk[0.001].step_time <= pk[0.01].step_time + 1e-9
    assert pk[0.01].mfu <= pk[0.001].mfu <= fl.mfu
    rows.append(("train.smollm-135m.P16.loss1pct_step_x",
                 round(pk[0.01].step_time / fl.step_time, 4),
                 f"packet(q=1%) vs fluid; mfu {fl.mfu:.3f}->"
                 f"{pk[0.01].mfu:.3f}"))
    rows.append(("train.smollm-135m.P16.analytic_vs_fluid_x",
                 round(an.step_time / fl.step_time, 4),
                 "closed-form lower bound / fluid engine (<= 1)"))

    # ---- pipeline composition at scale (1F1B bubble is exact model math)
    pp_r = simulate_training_run("granite-34b", n_hosts=64, pp=4,
                                 grad_accum=8, policy="split", fabric=fab)
    assert pp_r.pipeline_bubble_fraction == (4 - 1) / (8 + 4 - 1)
    rows.append(("train.granite-34b.P64.pp4ga8.bubble_frac",
                 round(pp_r.bubble_fraction, 4),
                 f"dp={pp_r.dp} step={pp_r.step_time:.2f}s "
                 f"mfu={pp_r.mfu:.3f} "
                 f"pipe_bubble={pp_r.pipeline_bubble_fraction:.3f}"))

    rows.append(("train.sweep_wall_s",
                 round(time.perf_counter() - t0, 3),
                 "3 models x 3 scales + routed policy pair + loss curve"))
    return rows


def measured_protocol_micro():
    """Measured on THIS machine: protocol hot-path microbenchmarks (us/call)."""
    rows = []
    buf = bytes(np.random.default_rng(0).integers(0, 256, 1 << 20, dtype=np.uint8))
    t0 = time.perf_counter()
    chunks = protocol.segment(buf)
    dt = (time.perf_counter() - t0) * 1e6
    rows.append(("micro.segment_1MiB_us", round(dt, 1), f"{len(chunks)} chunks"))
    leaf = protocol.LeafReceiver(len(buf))
    t0 = time.perf_counter()
    for c in chunks:
        leaf.deliver(c)
    dt = (time.perf_counter() - t0) * 1e6 / len(chunks)
    rows.append(("micro.deliver_per_chunk_us", round(dt, 2), "bitmap+copy"))
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    r = simulate_broadcast(32, 1 << 20, FabricParams(p_drop=0.001),
                           WorkerParams(8), rng)
    dt = (time.perf_counter() - t0) * 1e6
    rows.append(("micro.simulate_bcast32_us", round(dt, 0),
                 f"recovered={r.recovered}"))
    return rows


def measured_jax_collectives():
    """Measured on THIS machine (8 fake CPU devices, subprocess): wall time of
    the shard_map collective kernels. The host has no duplex ICI links, so
    bidi/concurrent gains show structurally (validated in tests), not in
    host wall-clock; the rows document measured reality."""
    import os
    import subprocess
    import sys

    code = """
import time, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
n = 1 << 20
full = jnp.arange(8 * n, dtype=jnp.float32)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
per_dev = jnp.stack([full * (i + 1) for i in range(8)])
def t(f, *a):
    f(*a)[0].block_until_ready() if isinstance(f(*a), tuple) else jax.block_until_ready(f(*a))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(*a))
    return (time.perf_counter() - t0) / 5 * 1e6
for mode in ['ring', 'bidi', 'bcast']:
    ag = C.make_allgather(mesh, 'x', mode, n_chains=4 if mode == 'bcast' else None)
    print(f'collective.allgather_{mode}_32MB_us,{t(ag, sharded):.0f},measured 8dev')
rs = C.make_reduce_scatter(mesh, 'x', 'bidi')
print(f'collective.reduce_scatter_bidi_32MB_us,{t(rs, per_dev.reshape(-1)):.0f},measured 8dev')
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # host devices, even where a TPU is attached
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    rows = []
    for line in res.stdout.splitlines():
        if line.startswith("collective."):
            name, val, der = line.split(",", 2)
            rows.append((name, val, der))
    assert rows, res.stderr[-2000:]
    return rows


ALL = [
    fig2_traffic_model, fig5_cpu_datapath, fig10_critical_path,
    fig11_throughput_188, fig12_traffic_savings, table1_datapath,
    fig13_14_thread_scaling, fig15_chunk_sizes, fig16_tbit,
    appendix_b_speedup, dpa_scaling_sweep, fsdp_contention_sweep,
    fabric_sweep, protocol_loss_sweep, packet_scale_sweep,
    multi_job_contention,
    schedule_ir_sweep, search_sweep, hier_fabric_sweep,
    training_run_sweep,
    measured_protocol_micro, measured_jax_collectives,
]

# seconds-scale subset for benchmarks/run.py --smoke / CI: the FSDP
# contention grid, the routed fabric sweep (capped at 512 hosts so its
# traffic-conservation and Insight-1 asserts run on every check in < ~60 s),
# the packet-protocol loss sweep (constant-time recovery + unicast
# crossover), the event-level DPA scaling sweep (Figs 13/14/16 + offload
# economics), the multi-job contention scenario and the schedule-IR
# allreduce-vs-ring sweep (ring/mcast time + fabric-byte ratios, autotune),
# the packet-engine scale sweep (vectorized-vs-reference wall-clock,
# including the 10k-host / 1 GiB speedup floor), and the tiered island
# fabric sweep (searched mixed-transport allgather vs flat builders with
# per-tier fabric-byte relief at P=64/256 — the ISSUE-8 acceptance gates),
# and the training-run co-sim sweep (GPT-small -> 34B step time / MFU /
# bubble fraction at 16-256 hosts, split-vs-naive MFU win, loss curve)
SMOKE = [fsdp_contention_sweep, fabric_sweep_smoke, protocol_loss_sweep_smoke,
         dpa_scaling_smoke, multi_job_contention, schedule_ir_sweep,
         search_sweep, packet_scale_sweep_smoke, hier_fabric_sweep,
         training_run_sweep]
