"""Model-mesh gateway fleet benchmark (beyond paper): >=3 models behind one
router with heterogeneous traffic (Poisson stream, burst + canary split, and
a sparse workload forcing a scale-to-zero -> cold-start cycle), plus a
placement plan across >=2 cloud profiles under both objectives, plus an
SLO/failover scenario (three traffic classes through a mid-run cloud outage
vs a no-priority baseline on the same seed), plus an active-active
split-vs-single-cloud scenario: the same capacity-constrained demand placed
single-cloud and split, raced on identical traffic -- the split must win on
at least one of {p99, simulated cost} -- plus an OVERLOAD scenario (ISSUE
4): stale split weights over unequal capacity, offered load past the
fleet's ceiling, raced queue-aware-routing-plus-shedding vs pure weighted
routing on the same seed -- queue-aware must win latency-class p99 while
reporting a nonzero, bounded shed rate (batch work never shed) -- plus two
OBSERVABILITY scenarios (ISSUE 6): the overload race re-run with the full
telemetry plane attached (burn-rate monitor + tracer + metrics), where the
SLO alert must fire no later than the first replan migrate and the trace
analyzer's per-stage latency-breakdown table is derived from the spans;
and an instrumentation-overhead race (the same stream run bare and fully
instrumented on one seed) that must keep the traced hot-loop wall within
10% of untraced while leaving the simulation outcome bit-identical --
plus a DRIFT scenario (ISSUE 10): the profiling DAG measures the backend into
ModelProfile artifacts per cloud, a profile-planned placement (every
demand number from the store, zero hand-tuned constants) races the
hand-tuned plan within 1.1x on p99, then an injected service-time shift
must fire profile:drift strictly before the first
``gateway:migrate reason=profile_drift`` (seq-ordered, asserted), and
every bench event log carries only registered event kinds.

Every scenario also lands in ``benchmarks/BENCH_gateway.json`` (per-scenario
p50/p99, deadline-miss rates, shed rates, simulated dollars; schema
validated by ``validate_bench``) so the perf trajectory is tracked across
PRs instead of being print-only.  ``python benchmarks/bench_gateway.py
--smoke`` runs only the overload + observability scenarios + schema
validation (the CI bench-smoke step).

Compute service times are measured (jitted matmuls of three widths); the
network / cold-start / price terms come from the CloudProfiles: any dollar
or RTT figure here is a simulation output (DESIGN.md §1)."""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from ._schema import check_header, require_keys
except ImportError:                      # run directly as a script (CI)
    from _schema import check_header, require_keys

from repro.clouds.capacity import CapacityMarket
from repro.clouds.profiles import TPU_V5E, CloudProfile, get_profile
from repro.core.pipeline import Pipeline
from repro.pipelines import Orchestrator, RetryPolicy
from repro.serving.gateway import (SLO_CLASSES, AdmissionConfig,
                                   AutoscalerConfig, CloudCapacity,
                                   FailureSpec, Gateway, ModelDemand,
                                   Predictor, ReplanConfig, RoutingConfig,
                                   SLOClass, TrafficSpec, plan_placement)
from repro.modelci import ProfileSpec, ProfileStore, finalize, measure
from repro.pipelines import DeploySpec
from repro.telemetry.analyze import request_table, slowest_requests
from repro.telemetry.drift import DriftConfig
from repro.telemetry.events import EventLog, unregistered
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.slo import BurnRateConfig
from repro.telemetry.trace import Tracer

BENCH_JSON = pathlib.Path(__file__).resolve().parent / "BENCH_gateway.json"
# schema 8: "drift" tier (profile-planned placement vs hand-tuned +
# injected service-time shift through the DriftMonitor, ISSUE 10);
# schema 7 added the "contention" tier (training colocated with a serving
# burst on one CapacityMarket, priority on vs off, ISSUE 9)
BENCH_SCHEMA = 8

WIDTHS = {"small": 64, "medium": 128, "large": 256}
# fleet-scale offered load in Erlangs (rate derived from the measured
# service time, so the plan shape is host-independent); the simulated
# streams below are scaled-down samples of the same mix
PLANNED_LOADS = {"small": 4.0, "medium": 2.0, "large": 0.5}


def _make_predictor(name: str, width: int, seed: int = 0) -> Predictor:
    w = jax.random.normal(jax.random.PRNGKey(seed), (width, width), jnp.float32)
    predict = jax.jit(lambda v: jnp.tanh(v @ w))
    p = Predictor(name, predict, np.zeros((1, width), np.float32))
    p.warmup((1, 8, 32))
    return p


def _round(x, nd: int):
    """None-preserving round: empty pools report null percentiles."""
    return None if x is None else round(x, nd)


def _model_record(res, cold: int) -> dict:
    return {"p50_s": _round(res.p50, 6), "p99_s": _round(res.p99, 6),
            "sim_cost_usd": round(res.cost_usd, 8),
            "cold_starts": cold,
            "shed": res.shed_total,
            "shed_rate": round(res.shed_rate, 4),
            "deadline_miss": {c: s["miss_rate"]
                              for c, s in res.per_class().items()}}


def validate_bench(bench: dict, require: tuple = ()) -> None:
    """BENCH_gateway.json schema check (the CI bench-smoke gate): the
    shared header/required-scenario machinery lives in ``_schema``; the
    suite-specific semantic gates below cover every scenario present --
    including the ISSUE 4 shed-rate fields, the recorded
    queue-aware-vs-weights race and the ISSUE 9 contention ratios."""
    sc = check_header(bench, BENCH_SCHEMA, require)
    for name, rec in sc.get("fleet", {}).get("models", {}).items():
        require_keys(rec, ("p50_s", "p99_s", "sim_cost_usd", "cold_starts",
                           "shed", "shed_rate", "deadline_miss"),
                     f"fleet model {name}")
    for key in ("slo_failover", "split_cost"):
        if key in sc and not sc[key]:
            raise ValueError(f"scenario {key} is empty")
    if "overload" in sc:
        o = sc["overload"]
        require_keys(o, ("queue_aware", "weights", "race", "burn"),
                     "overload scenario")
        for side in ("queue_aware", "weights"):
            require_keys(o[side], ("per_class", "shed", "shed_rate"),
                         f"overload.{side}")
        race = o["race"]
        require_keys(race, ("winner", "latency_p99_queue_aware",
                            "latency_p99_weights", "shed_rate"),
                     "overload race")
        if not 0 < race["shed_rate"] <= 0.5:
            raise ValueError(f"shed rate {race['shed_rate']} not in (0, .5]")
        burn = o["burn"]
        require_keys(burn, ("alerts_firing", "first_alert_seq",
                            "first_migrate_seq", "scrapes", "spans",
                            "slowest_request"), "overload burn")
        if burn["alerts_firing"] < 1:
            raise ValueError("overload burn run recorded no firing alert")
        if (burn["first_migrate_seq"] is not None
                and burn["first_alert_seq"] > burn["first_migrate_seq"]):
            raise ValueError("burn alert fired after the first migrate")
    if "scale" in sc:
        s5 = sc["scale"]
        require_keys(s5, ("requests", "models", "clouds", "oracle_requests",
                          "scalar", "vector", "speedup",
                          "asserted_min_speedup"), "scale scenario")
        for side in ("scalar", "vector"):
            require_keys(s5[side], ("wall_s", "sim_events", "events_per_s",
                                    "requests_per_s"), f"scale.{side}")
        if s5["speedup"] < s5["asserted_min_speedup"]:
            raise ValueError(
                f"scale speedup {s5['speedup']}x below the asserted "
                f"{s5['asserted_min_speedup']}x floor")
        # the full tier must really be the >=10^6-request scenario
        if s5["asserted_min_speedup"] >= 50 and s5["requests"] < 10 ** 6:
            raise ValueError(f"scale tier ran only {s5['requests']} requests")
    if "observability" in sc:
        ob = sc["observability"]
        require_keys(ob, ("wall_untraced_s", "wall_traced_s",
                          "overhead_frac", "materialize_wall_s", "spans",
                          "scrapes"), "observability scenario")
        # walls are host-measured (noise can push the min-of-pairs ratio
        # slightly negative); the asserted gate is the 10% ceiling
        if not -0.5 < ob["overhead_frac"] < 0.10:
            raise ValueError(
                f"instrumentation overhead {ob['overhead_frac']} >= 10%")
    if "drift" in sc:
        dr = sc["drift"]
        require_keys(dr, ("hand_p99_s", "profile_p99_s", "p99_ratio",
                          "profiles_committed", "injected_factor", "drift",
                          "scrapes"), "drift scenario")
        if dr["p99_ratio"] > 1.1:
            raise ValueError(f"profile-planned p99 {dr['p99_ratio']}x the "
                             "hand-tuned plan (> 1.1x gate)")
        if dr["profiles_committed"] < 2:
            raise ValueError("profiling DAG committed fewer than 2 "
                             "per-cloud artifacts")
        d = dr["drift"]
        require_keys(d, ("firing", "ratio", "first_drift_seq",
                         "first_migrate_seq", "migrates_profile_drift",
                         "reprofile_armed"), "drift.drift")
        if d["firing"] < 1:
            raise ValueError("injected shift never fired profile:drift")
        if d["migrates_profile_drift"] < 1:
            raise ValueError("drift never armed a profile_drift migrate")
        if d["first_drift_seq"] > d["first_migrate_seq"]:
            raise ValueError("profile:drift fired after the first "
                             "reason=profile_drift migrate")
    if "contention" in sc:
        ct = sc["contention"]
        require_keys(ct, ("slots", "dedicated", "priority_on",
                          "priority_off", "training", "p99_ratio",
                          "makespan_ratio"), "contention scenario")
        require_keys(ct["priority_on"], ("p99_s", "preempts", "leases",
                                         "scale_denied"),
                     "contention.priority_on")
        require_keys(ct["priority_off"], ("p99_s", "preempts",
                                          "scale_denied"),
                     "contention.priority_off")
        require_keys(ct["training"], ("contended_makespan_s",
                                      "uncontended_makespan_s", "preempts",
                                      "exactly_once"), "contention.training")
        if ct["priority_on"]["preempts"] < 1:
            raise ValueError("contention priority-on leg never preempted")
        if ct["priority_on"]["scale_denied"] != 0:
            raise ValueError("priority-on serving was starved by training")
        if ct["priority_off"]["scale_denied"] < 1:
            raise ValueError("priority-off leg never hit a capacity denial")
        if ct["p99_ratio"] > 1.3:
            raise ValueError(f"contended serving p99 {ct['p99_ratio']}x the "
                             "dedicated baseline (> 1.3x gate)")
        if ct["makespan_ratio"] > 2.0:
            raise ValueError("contended training makespan "
                             f"{ct['makespan_ratio']}x uncontended (> 2x)")
        if not ct["training"]["exactly_once"]:
            raise ValueError("preempted training attempts broke exactly-once")


def run() -> list[dict]:
    preds = {n: _make_predictor(n, w) for n, w in WIDTHS.items()}
    bench: dict = {"schema": BENCH_SCHEMA, "scenarios": {}}

    # -- placement: both objectives over gcp/ibm ---------------------------
    demands = [ModelDemand(n, PLANNED_LOADS[n] / (preds[n].service_time(8) / 8),
                           preds[n].service_time(8) / 8)
               for n in WIDTHS]
    # gcp is cheaper but capacity-constrained, so the cost plan itself must
    # spill part of the fleet onto ibm (a genuinely multi-cloud placement)
    clouds = [CloudCapacity(get_profile("gcp"), 8, 1.0),
              CloudCapacity(get_profile("ibm"), 16, 1.4)]
    plans = {obj: plan_placement(demands, clouds, objective=obj)
             for obj in ("cost", "p99")}
    plan = plans["cost"]
    # measured service times vary by host: an unplaceable model would give
    # cloud=None below, so fail with the plan rather than a KeyError
    assert plan.feasible, plan.summary()
    cloud_of = {a.model: a.cloud for a in plan.assignments}

    # -- fleet simulation on the cost plan ---------------------------------
    log = EventLog()
    gw = Gateway(capacity=plan.capacity_map(), log=log)
    replicas = {a.model: a.replicas for a in plan.assignments}
    gw.deploy("small", preds["small"], get_profile(cloud_of["small"]),
              autoscaler=AutoscalerConfig(
                  min_replicas=1, max_replicas=replicas["small"],
                  target_queue=8, idle_window_s=2.0), max_batch=16)
    gw.deploy("medium", preds["medium"], get_profile(cloud_of["medium"]),
              autoscaler=AutoscalerConfig(
                  min_replicas=1, max_replicas=replicas["medium"],
                  target_queue=8, idle_window_s=2.0), max_batch=16,
              canary=_make_predictor("medium-canary", WIDTHS["medium"], seed=1),
              canary_fraction=0.2)
    gw.deploy("large", preds["large"], get_profile(cloud_of["large"]),
              autoscaler=AutoscalerConfig(
                  min_replicas=0, max_replicas=max(replicas["large"], 1),
                  scale_up_delay_s=0.5, idle_window_s=1.0), max_batch=8)
    out = gw.run([
        TrafficSpec("small", 600, arrival="poisson", rate=2000.0),
        TrafficSpec("medium", 256),                      # burst + canary
        TrafficSpec("large", 8),                         # cold start #1
        TrafficSpec("large", 8, start_s=6.0),            # idle -> cold #2
    ], seed=0)

    rows = []
    for name, res in out.per_model.items():
        trace = res.replica_trace
        rows.append({
            "name": f"gateway_{name}",
            "us_per_call": res.p50 * 1e6,
            "derived": f"cloud={cloud_of[name]};p50_s={res.p50:.5f};"
                       f"p99_s={res.p99:.5f};replicas_max="
                       f"{max(r for _, r in trace)};"
                       f"cold_starts={out.cold_starts[name]};"
                       f"hit_zero={any(r == 0 for _, r in trace[1:])}",
        })
    bench["scenarios"]["fleet"] = {
        "models": {m: _model_record(r, out.cold_starts[m])
                   for m, r in out.per_model.items()},
        "sim_cost_usd": round(out.total_cost_usd, 8),
        "makespan_s": round(out.makespan_s, 6)}
    for obj, pl in plans.items():
        s = pl.summary()
        assign = ";".join(f"{m}->{a['cloud']}x{a['replicas']}"
                          for m, a in s["assignments"].items())
        rows.append({
            "name": f"gateway_placement_{obj}",
            "us_per_call": float(pl.worst_p99_s) * 1e6,
            "derived": f"feasible={s['feasible']};"
                       f"cost_hr={s['total_cost_hr']};{assign}",
        })
    rows.append({
        "name": "gateway_events",
        "us_per_call": out.makespan_s * 1e6,
        "derived": f"cold_start={log.count('gateway:cold_start')};"
                   f"scale_up={log.count('gateway:scale_up')};"
                   f"scale_down={log.count('gateway:scale_down')};"
                   f"scale_to_zero={log.count('gateway:scale_to_zero')}",
    })
    # acceptance: the large model must complete a scale-to-zero -> cold-start
    # cycle (zero pool between its two bursts, a cold start on each)
    assert out.cold_starts["large"] >= 2, out.cold_starts
    assert any(r == 0 for _, r in out.per_model["large"].replica_trace[1:])
    rows.extend(_slo_failover_scenario(preds["large"], bench))
    rows.extend(_split_cost_scenario(preds["medium"], bench))
    rows.extend(_overload_shed_scenario(preds["small"], bench))
    rows.extend(_observability_scenario(preds["small"], bench))
    rows.extend(_drift_scenario(preds["small"], bench))
    rows.extend(_contention_scenario(preds["small"], bench))
    rows.extend(_scale_scenario(bench))
    validate_bench(bench, require=("fleet", "slo_failover", "split_cost",
                                   "overload", "observability", "drift",
                                   "contention", "scale"))
    BENCH_JSON.write_text(json.dumps(bench, indent=1, sort_keys=True))
    print(f"wrote {BENCH_JSON}", file=sys.stderr)
    return rows


def _slo_failover_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """Three SLO classes on one two-replica fleet, a mid-run gcp outage with
    ibm standby, against a no-priority baseline (uniform class weights, no
    preemption -- same class NAMES so the per-class tables line up) on the
    same seed.  Timing is derived from the measured batch service time so
    the backlog shape is host-independent."""
    prof = get_profile("gcp")
    t8 = pred.service_time(8)
    per_batch = prof.network_rtt_s + prof.lb_overhead_s + t8
    n_batch = 480
    drain_s = (n_batch / 8) * per_batch / 2      # backlog of the batch burst
    window_s = 2.0 * drain_s
    outage = FailureSpec("gcp", at_s=0.3 * drain_s,
                         duration_s=max(0.4 * drain_s, 0.25))

    def classes(priority: bool) -> dict:
        if priority:
            return {c: SLO_CLASSES[c] for c in ("latency", "standard",
                                                "batch")}
        return {c: SLOClass(c, 1.0, SLO_CLASSES[c].deadline_mult)
                for c in ("latency", "standard", "batch")}

    def run_once(priority: bool):
        cls = classes(priority)
        log = EventLog()
        gw = Gateway(log=log)
        gw.deploy("fleet", pred, prof, standby=get_profile("ibm"),
                  autoscaler=AutoscalerConfig(
                      min_replicas=2, max_replicas=2,
                      scale_up_delay_s=0.005, idle_window_s=np.inf),
                  max_batch=8)
        out = gw.run([
            TrafficSpec("fleet", n_batch, slo=cls["batch"]),
            TrafficSpec("fleet", 120, slo=cls["standard"],
                        arrival="poisson", rate=120 / window_s),
            TrafficSpec("fleet", 80, slo=cls["latency"],
                        arrival="poisson", rate=80 / window_s),
        ], seed=0, failures=[outage])
        return out, log

    pri, pri_log = run_once(priority=True)
    base, _ = run_once(priority=False)
    pc, bc = pri.per_class(), base.per_class()

    print("per-class p99 (priority dispatch vs no-priority baseline, "
          "same seed + same gcp outage):", file=sys.stderr)
    print(f"  {'class':<10}{'p99_s':>12}{'baseline':>12}{'miss_rate':>12}",
          file=sys.stderr)
    for c in ("latency", "standard", "batch"):
        print(f"  {c:<10}{pc[c]['p99_s']:>12.5f}{bc[c]['p99_s']:>12.5f}"
              f"{pc[c]['miss_rate']:>12.4f}", file=sys.stderr)

    # acceptance: priority dispatch must strictly beat the baseline for the
    # latency class, and the outage must actually have moved the fleet
    assert pc["latency"]["p99_s"] < bc["latency"]["p99_s"], (pc, bc)
    assert pri_log.count("gateway:failover") >= 1
    assert pri_log.count("gateway:recover") >= 1

    bench["scenarios"]["slo_failover"] = {
        "classes": pc,
        "baseline": bc,
        "sim_cost_usd": round(pri.total_cost_usd, 8),
        "events": {k: pri_log.count(f"gateway:{k}")
                   for k in ("failover", "recover", "preempt", "cold_start",
                             "split")}}
    rows = [{"name": f"gateway_slo_{c}",
             "us_per_call": pc[c]["p99_s"] * 1e6,
             "derived": f"p50_s={pc[c]['p50_s']:.5f};"
                        f"p99_s={pc[c]['p99_s']:.5f};"
                        f"baseline_p99_s={bc[c]['p99_s']:.5f};"
                        f"miss_rate={pc[c]['miss_rate']}"}
            for c in ("latency", "standard", "batch")]
    rows.append({
        "name": "gateway_slo_failover",
        "us_per_call": pri.makespan_s * 1e6,
        "derived": f"outage_at_s={outage.at_s:.4f};"
                   f"outage_s={outage.duration_s:.4f};"
                   f"failover={pri_log.count('gateway:failover')};"
                   f"recover={pri_log.count('gateway:recover')};"
                   f"preempt={pri_log.count('gateway:preempt')};"
                   f"cold_start={pri_log.count('gateway:cold_start')}",
    })
    return rows


def _split_cost_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """Active-active acceptance (ISSUE 3): one demand that needs more
    replicas than the cheap cloud can hold, placed two ways on the SAME
    measured service time and raced on the SAME traffic/seed --
    single-cloud (forced all-expensive by capacity) vs split (cheap first,
    spill the remainder).  The split must beat single-cloud on p99 or
    simulated cost.  The traffic is open-loop UNDERLOAD -- both fleets are
    provisioned for the window, so the makespan is pinned by the arrival
    stream and the split's cheaper replica-seconds (2x gcp@1.0 + 2x
    ibm@1.4 vs 4x ibm@1.4) decide the bill."""
    t1 = pred.service_time(8) / 8        # per-request service, batched
    need = 4
    demand = ModelDemand("ranker", rate=0.7 * need / t1, service_time_s=t1)
    clouds = [CloudCapacity(get_profile("gcp"), 2, 1.0),   # cheap, small
              CloudCapacity(get_profile("ibm"), 8, 1.4)]   # fast, dear
    single = plan_placement([demand], clouds, objective="cost")
    split = plan_placement([demand], clouds, objective="cost", split=True)
    assert single.assignments[0].shares == {"ibm": need}   # gcp can't fit it
    assert split.assignments[0].shares == {"gcp": 2, "ibm": 2}

    # ~60% of the SLOWER pool's throughput share (gcp per-batch path is the
    # long pole), derived from measured+profile terms so any host lands in
    # the same utilization regime
    prof = get_profile("gcp")
    per_batch = prof.network_rtt_s + prof.lb_overhead_s + pred.service_time(8)
    n = 600
    traffic = [TrafficSpec("ranker", n, arrival="poisson",
                           rate=19.2 / per_batch)]

    def run_once(assignment):
        gw = Gateway()
        gw.deploy("ranker", pred,
                  split={get_profile(c): w
                         for c, w in assignment.weights.items()},
                  autoscaler=AutoscalerConfig(min_replicas=need,
                                              max_replicas=need,
                                              idle_window_s=np.inf),
                  max_batch=8)
        return gw.run(traffic, seed=0)

    out_single = run_once(single.assignments[0])
    out_split = run_once(split.assignments[0])
    r_single = out_single.per_model["ranker"]
    r_split = out_split.per_model["ranker"]
    wins = []
    if r_split.p99 < r_single.p99:
        wins.append("p99")
    if out_split.total_cost_usd < out_single.total_cost_usd:
        wins.append("cost")
    print(f"split vs single-cloud: p99 {r_split.p99:.5f} vs "
          f"{r_single.p99:.5f}, sim $ {out_split.total_cost_usd:.6f} vs "
          f"{out_single.total_cost_usd:.6f} -> wins={wins}", file=sys.stderr)
    # acceptance: active-active must beat single-cloud on at least one axis
    assert wins, (r_split.p99, r_single.p99, out_split.total_cost_usd,
                  out_single.total_cost_usd)

    bench["scenarios"]["split_cost"] = {
        "single": {"p50_s": round(r_single.p50, 6),
                   "p99_s": round(r_single.p99, 6),
                   "sim_cost_usd": round(out_single.total_cost_usd, 8),
                   "plan": single.summary()["assignments"]},
        "split": {"p50_s": round(r_split.p50, 6),
                  "p99_s": round(r_split.p99, 6),
                  "sim_cost_usd": round(out_split.total_cost_usd, 8),
                  "plan": split.summary()["assignments"]},
        "wins": wins}
    return [{
        "name": "gateway_split_vs_single",
        "us_per_call": r_split.p99 * 1e6,
        "derived": f"wins={'+'.join(wins)};"
                   f"split_p99_s={r_split.p99:.5f};"
                   f"single_p99_s={r_single.p99:.5f};"
                   f"split_cost={out_split.total_cost_usd:.6f};"
                   f"single_cost={out_single.total_cost_usd:.6f}",
    }]


def _overload_shed_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """Overload acceptance (ISSUE 4): a STALE 50/50 split over unequal
    capacity (ibm is capacity-pinned at one replica, gcp can grow to two)
    under offered load past the whole fleet's ceiling, raced two ways on
    the same seed and traffic:

      weights      pure weighted routing, no admission control -- half of
                   everything piles onto the one ibm replica;
      queue_aware  the ISSUE 4 blend -- requests join the best expected
                   queue, deadline-hopeless latency/standard work is shed
                   (exactly once, batch only deferred), and shed-pressure
                   still drives scale-up.

    queue-aware + shedding must win latency-class p99 while reporting a
    NONZERO but BOUNDED (<= 0.5) shed rate.  Timing derives from the
    measured batch service time so every host lands in the same
    utilization regime."""
    t8 = pred.service_time(8)
    prof = get_profile("gcp")
    per_batch = prof.network_rtt_s + prof.lb_overhead_s + t8
    cap_rps = 3 * 8 / per_batch          # 3-replica fleet ceiling
    window_s = 40 * per_batch
    n_batch = int(0.5 * cap_rps * window_s)      # burst backlog, never shed
    n_std = int(0.6 * cap_rps * window_s)
    n_lat = int(0.3 * cap_rps * window_s)
    traffic = [
        TrafficSpec("m", n_batch, slo="batch"),
        TrafficSpec("m", n_std, arrival="poisson", rate=n_std / window_s),
        TrafficSpec("m", n_lat, slo="latency",
                    arrival="poisson", rate=n_lat / window_s),
    ]

    def run_once(queue_aware: bool, burn: bool = False):
        log = EventLog()
        extra: dict = {}
        if burn:
            # full telemetry plane: burn-rate monitor (windows derived
            # from the measured batch time so alerts land in the same sim
            # regime on any host) + replan it can arm + tracer + scrapes
            extra = dict(
                replan=ReplanConfig(check_every_s=4 * per_batch,
                                    sustain=3, shift=0.25),
                slo_burn=BurnRateConfig(short_s=4 * per_batch,
                                        long_s=12 * per_batch),
                tracer=Tracer(), metrics=MetricsRegistry(),
                scrape_every_s=5 * per_batch)
        gw = Gateway(capacity={"gcp": 3, "ibm": 1}, log=log,
                     routing=RoutingConfig(
                         "queue_aware" if queue_aware else "weights"),
                     admission=AdmissionConfig() if queue_aware else None,
                     **extra)
        gw.deploy("m", pred,
                  split={get_profile("gcp"): 0.5, get_profile("ibm"): 0.5},
                  autoscaler=AutoscalerConfig(min_replicas=2, max_replicas=4,
                                              target_queue=8,
                                              scale_up_delay_s=0.01,
                                              idle_window_s=np.inf),
                  max_batch=8)
        return gw.run(traffic, seed=0), log, gw

    out_q, log_q, _ = run_once(queue_aware=True)
    out_w, _, _ = run_once(queue_aware=False)
    res_q, res_w = out_q.per_model["m"], out_w.per_model["m"]
    pc_q, pc_w = res_q.per_class(), res_w.per_class()
    # a fully shed class reports p99_s=None; fail with the scenario stats
    # rather than a TypeError in the table / comparison below
    assert all(pc[c]["n"] > 0 for pc in (pc_q, pc_w)
               for c in ("latency", "standard", "batch")), \
        f"a class was fully shed -- retune the overload regime: {pc_q}"

    print("overload race (queue-aware + shedding vs pure weights, "
          "same seed, 50/50 split over 2:1 capacity):", file=sys.stderr)
    print(f"  {'class':<10}{'qa_p99_s':>12}{'w_p99_s':>12}{'qa_shed':>9}",
          file=sys.stderr)
    for c in ("latency", "standard", "batch"):
        print(f"  {c:<10}{pc_q[c]['p99_s']:>12.5f}{pc_w[c]['p99_s']:>12.5f}"
              f"{pc_q[c]['shed']:>9}", file=sys.stderr)
    print(f"  shed rate {res_q.shed_rate:.4f} "
          f"({res_q.shed_total}/{res_q.n_requests})", file=sys.stderr)

    # acceptance: queue-aware + shedding beats pure weights on the latency
    # class tail; the shed rate is nonzero but bounded; batch is intact
    assert pc_q["latency"]["p99_s"] < pc_w["latency"]["p99_s"], (pc_q, pc_w)
    assert 0 < res_q.shed_rate <= 0.5, res_q.shed_rate
    assert res_q.class_shed.get("batch", 0) == 0
    assert len(res_q.class_latencies["batch"]) == n_batch
    assert res_w.shed_total == 0         # baseline admits everything
    # shedding must not mask the overload from the autoscaler
    assert log_q.count("gateway:scale_up") >= 1

    # third run (ISSUE 6): same traffic with the burn-rate monitor, replan
    # and the tracer/metrics plane attached -- the SLO alert must lead (or
    # tie with) the first replan migrate in event order, and the slowest
    # request's stage breakdown is derived from the span tree
    _, log_b, gw_b = run_once(queue_aware=True, burn=True)
    alerts = [e for e in log_b.named("gateway:alert")
              if e["state"] == "firing"]
    migrates = log_b.named("gateway:migrate")
    assert alerts, "burn monitor never fired under sustained overload"
    if migrates:
        assert alerts[0]["seq"] <= migrates[0]["seq"], (alerts[0],
                                                        migrates[0])
    print(request_table(gw_b.tracer, 3), file=sys.stderr)
    slow = slowest_requests(gw_b.tracer, 1)[0]

    bench["scenarios"]["overload"] = {
        "burn": {
            "alerts_firing": len(alerts),
            "first_alert_seq": alerts[0]["seq"],
            "first_migrate_seq": (migrates[0]["seq"] if migrates
                                  else None),
            "migrates": len(migrates),
            "scrapes": log_b.count("metrics:scrape"),
            "spans": len(gw_b.tracer.spans),
            "slowest_request": {
                k: round(v, 6) if isinstance(v, float) else v
                for k, v in slow.items()}},
        "queue_aware": {"per_class": pc_q, "shed": res_q.shed_total,
                        "shed_rate": round(res_q.shed_rate, 4),
                        "sim_cost_usd": round(out_q.total_cost_usd, 8)},
        "weights": {"per_class": pc_w, "shed": res_w.shed_total,
                    "shed_rate": 0.0,
                    "sim_cost_usd": round(out_w.total_cost_usd, 8)},
        "race": {"winner": "queue_aware",
                 "latency_p99_queue_aware": pc_q["latency"]["p99_s"],
                 "latency_p99_weights": pc_w["latency"]["p99_s"],
                 "shed_rate": round(res_q.shed_rate, 4),
                 "scale_ups_queue_aware":
                     log_q.count("gateway:scale_up")}}
    return [{
        "name": "gateway_overload_race",
        "us_per_call": pc_q["latency"]["p99_s"] * 1e6,
        "derived": f"qa_latency_p99_s={pc_q['latency']['p99_s']:.5f};"
                   f"w_latency_p99_s={pc_w['latency']['p99_s']:.5f};"
                   f"shed_rate={res_q.shed_rate:.4f};"
                   f"shed={res_q.shed_total};"
                   f"batch_shed={res_q.class_shed.get('batch', 0)}",
    }, {
        "name": "gateway_burn_alerts",
        "us_per_call": slow["total_s"] * 1e6,
        "derived": f"alerts_firing={len(alerts)};"
                   f"first_alert_seq={alerts[0]['seq']};"
                   f"migrates={len(migrates)};"
                   f"spans={len(gw_b.tracer.spans)};"
                   f"scrapes={log_b.count('metrics:scrape')}",
    }]


def _observability_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """Instrumentation-overhead acceptance (ISSUE 6): the SAME mixed-class
    stream through the same queue-aware fleet, run bare and run with the
    full passive telemetry plane (tracer + metrics + periodic scrapes), on
    one seed.  Telemetry must be an OBSERVER: the two simulations must
    produce identical summaries, and the instrumented hot loop must stay
    within 10% of the bare wall.  Both walls are the min over interleaved
    pairs (back-to-back runs share the box's thermal state, so the ratio
    of mins is the noise-robust estimator) with the cyclic GC held off
    during the timed loop (the instrumented side allocates more young
    objects, so free-running gen-0 pauses land asymmetrically and can
    double the apparent overhead); the deferred span materialization --
    the collector flush that happens AFTER the event loop, like an async
    span processor draining -- is reported separately as
    materialize_wall_s, not charged to the hot loop."""
    t8 = pred.service_time(8)
    prof = get_profile("gcp")
    per_batch = prof.network_rtt_s + prof.lb_overhead_s + t8
    # dense ~85% utilization of a 7-replica ceiling: per-request loop work
    # dominates, so the fixed per-scrape fold cost amortizes the way a
    # production gateway's would
    window_s = 60 * per_batch
    cap_rps = 7 * 8 / per_batch
    n_std = int(0.6 * cap_rps * window_s)
    n_bat = int(0.25 * cap_rps * window_s)
    traffic = [
        TrafficSpec("m", n_std, arrival="poisson", rate=n_std / window_s),
        TrafficSpec("m", n_bat, slo="batch",
                    arrival="poisson", rate=n_bat / window_s),
    ]
    # the makespan runs ~2x the arrival window (the batch backlog drains
    # after the streams end), so this yields a handful of scrapes per run
    # -- the Prometheus-like regime where scrape cost amortizes
    scrape_s = window_s / 2
    # the drift monitor rides the scrape loop, so its per-scrape observe
    # cost belongs inside the same overhead gate (no replan is armed, so
    # the plane stays a pure observer)
    profile = finalize(measure(pred, max_batch=8), "m", get_profile("gcp"))

    def run_once(instrumented: bool):
        log = EventLog()
        gw = Gateway(capacity={"gcp": 4, "ibm": 3}, log=log,
                     routing=RoutingConfig("queue_aware"),
                     admission=AdmissionConfig(),
                     tracer=Tracer() if instrumented else None,
                     metrics=MetricsRegistry() if instrumented else None,
                     scrape_every_s=scrape_s if instrumented else None,
                     drift=DriftConfig() if instrumented else None)
        gw.deploy("m", pred,
                  split={get_profile("gcp"): 0.6, get_profile("ibm"): 0.4},
                  autoscaler=AutoscalerConfig(min_replicas=2, max_replicas=6,
                                              target_queue=8,
                                              idle_window_s=np.inf),
                  max_batch=8, planned_from=profile if instrumented else None)
        gc.collect()
        gc.disable()
        try:
            out = gw.run(traffic, seed=0)
        finally:
            gc.enable()
        return gw, out, log.named("gateway:run")[0]["wall_s"]

    wall_u = wall_t = float("inf")
    for _ in range(7):
        _, out_u, wu = run_once(instrumented=False)
        gw_t, out_t, wt = run_once(instrumented=True)
        # the plane is passive: same sim outcome to the last digit
        assert out_u.summary() == out_t.summary(), \
            "telemetry perturbed the simulation"
        wall_u, wall_t = min(wall_u, wu), min(wall_t, wt)
    overhead = wall_t / wall_u - 1.0
    mat = gw_t.log.named("trace:materialize")[0]["wall_s"]
    scrapes = gw_t.log.count("metrics:scrape")
    print(f"instrumentation overhead: untraced {wall_u * 1e3:.2f}ms "
          f"traced {wall_t * 1e3:.2f}ms ({overhead:+.1%}); span "
          f"materialization (off-loop) {mat * 1e3:.2f}ms, "
          f"{len(gw_t.tracer.spans)} spans, {scrapes} scrapes",
          file=sys.stderr)
    print(request_table(gw_t.tracer, 3), file=sys.stderr)
    # acceptance: the traced hot loop stays within 10% of untraced
    assert overhead < 0.10, f"instrumentation overhead {overhead:.1%}"

    bench["scenarios"]["observability"] = {
        "wall_untraced_s": round(wall_u, 6),
        "wall_traced_s": round(wall_t, 6),
        "overhead_frac": round(overhead, 4),
        "materialize_wall_s": round(mat, 6),
        "spans": len(gw_t.tracer.spans),
        "scrapes": scrapes,
        "requests": n_std + n_bat}
    return [{
        "name": "gateway_observability_overhead",
        "us_per_call": (wall_t - wall_u) / (n_std + n_bat) * 1e6,
        "derived": f"overhead_frac={overhead:.4f};"
                   f"wall_untraced_s={wall_u:.5f};"
                   f"wall_traced_s={wall_t:.5f};"
                   f"materialize_wall_s={mat:.5f};"
                   f"spans={len(gw_t.tracer.spans)};scrapes={scrapes}",
    }]


# -- model-CI drift tier (ISSUE 10): profile-planned placement + drift ------

class _ShiftBackend:
    """Serving backend whose cost model can be shifted BETWEEN runs (the
    drift injection).  The gateway samples service times once at run()
    start, so a mid-run mutation would be invisible; two runs sharing one
    EventLog keep the drift-before-migrate seq ordering assertable."""

    def __init__(self, inner, name: str):
        self.inner = inner
        self.name = name
        self.factor = 1.0

    def service_time(self, b: int) -> float:
        return self.factor * self.inner.service_time(b)


def _drift_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """Model-CI acceptance (ISSUE 10), two legs on one shared EventLog:

    race   the profiling DAG (two pinned ``kind="profile"`` steps measuring
           the same backend, one per cloud) commits ModelProfile artifacts
           into a ProfileStore, a ``DeploySpec(profile=store)`` deploy step
           plans the placement with EVERY demand number read from the
           store, and the resulting fleet races the hand-tuned plan (same
           measured service time entered as a constant) on identical
           traffic/seed: profile-planned p99 must stay within 1.1x.

    drift  the same deployment re-runs with the backend's service time
           shifted 1.6x (the profile is now stale).  The DriftMonitor must
           fire ``profile:drift`` (sustained out-of-band ratio at the
           scrape cadence), arm a re-profile (``modelci:reprofile``), and
           the replan probe must then migrate with reason=profile_drift --
           strictly AFTER the drift edge in event order (asserted on seq).

    Every event recorded across both legs must be registered vocabulary
    (``events.unregistered``)."""
    t8 = pred.service_time(8)
    svc = t8 / 8
    prof_g, prof_i = get_profile("gcp"), get_profile("ibm")
    per_batch = prof_g.network_rtt_s + prof_g.lb_overhead_s + t8
    clouds = [CloudCapacity(prof_g, 2, 1.0), CloudCapacity(prof_i, 2, 1.4)]
    load = 2.0          # planned fleet-scale Erlangs (3 replicas, 2 clouds)
    factor = 1.6        # injected shift; the stream below is sized so the
    # shifted fleet stays underloaded (~80% of the 3-replica per-batch
    # ceiling): ONLY the drift trigger may arm the probe -- no
    # overload/miss/shed signal competes for the migrate reason
    window_s = 60 * per_batch
    rate = 0.5 * 3 * 8 / per_batch       # 50% of the measured ceiling
    n = int(rate * window_s)
    traffic = [TrafficSpec("ranker", n, arrival="poisson", rate=rate)]
    asc = AutoscalerConfig(min_replicas=3, max_replicas=4, target_queue=8,
                           scale_up_delay_s=0.01, idle_window_s=np.inf)

    # hand-tuned leg: the same measured number, entered as a constant
    demand = ModelDemand("ranker", rate=load / svc, service_time_s=svc)
    hand = plan_placement([demand], clouds, objective="p99", split=True)
    ah = hand.assignments[0]
    assert hand.feasible and len(ah.shares) == 2, hand.summary()
    gw_h = Gateway(log=EventLog())
    gw_h.deploy("ranker", pred,
                split={get_profile(c): w for c, w in ah.weights.items()},
                autoscaler=asc, max_batch=8, queue_hint=dict(ah.est_wait_s))
    out_h = gw_h.run(traffic, seed=0)
    r_h = out_h.per_model["ranker"]

    # profile-planned leg: profiling DAG -> store -> deploy, one shared log
    store = ProfileStore()
    serve = _ShiftBackend(pred, "ranker")
    log = EventLog()
    pipe = Pipeline("model-ci")
    profs = [pipe.step(lambda: measure(pred, max_batch=8),
                       name=f"profile_{c}", cache=False, kind="profile",
                       pin=c, payload=ProfileSpec("ranker", store,
                                                  max_batch=8))
             for c in ("gcp", "ibm")]
    pipe.step(lambda *_: serve, *profs, name="deploy", cache=False,
              kind="deploy",
              payload=DeploySpec("ranker", clouds, load_erlangs=load,
                                 objective="p99", split=True,
                                 autoscaler=asc, max_batch=8,
                                 profile=store))
    gw_p = Gateway(log=log,
                   replan=ReplanConfig(check_every_s=4 * per_batch,
                                       sustain=2, shift=0.25,
                                       consolidate=False),
                   metrics=MetricsRegistry(),
                   drift=DriftConfig(threshold=1.3, sustain=2, min_n=8),
                   scrape_every_s=3 * per_batch)
    orch = Orchestrator({"gcp": 1, "ibm": 1}, log=log)
    rec = orch.execute(pipe.compile(), gateway=gw_p)
    assert rec.status == "succeeded", rec.steps
    assert log.count("modelci:profile") >= 2
    assert rec.outputs["deploy"]["profiled"] is True
    worst = store.worst("ranker")
    out_p = gw_p.run(traffic, seed=0)
    r_p = out_p.per_model["ranker"]
    ratio = r_p.p99 / r_h.p99

    # drift leg: shift the backend, re-run on the SAME gateway + log
    serve.factor = factor
    gw_p.run(traffic, seed=1)
    drifts = [e for e in log.named("profile:drift")
              if e["state"] == "firing"]
    migs = [e for e in log.named("gateway:migrate")
            if e["reason"] == "profile_drift"]
    reprof = sorted(gw_p.drift.pop_reprofile())

    print(f"drift tier: profile-planned p99 {r_p.p99:.5f}s vs hand-tuned "
          f"{r_h.p99:.5f}s ({ratio:.3f}x); shift {factor}x -> "
          f"{len(drifts)} drift edge(s), {len(migs)} profile_drift "
          f"migrate(s), reprofile armed for {reprof}", file=sys.stderr)

    # acceptance: the measured-artifact plan matches hand-tuning; the
    # injected shift is detected and ACTED on, detection strictly first
    assert ratio <= 1.1, (r_p.p99, r_h.p99)
    assert drifts, "injected shift never fired profile:drift"
    assert migs, "drift never armed a reason=profile_drift migrate"
    assert drifts[0]["seq"] <= migs[0]["seq"], (drifts[0], migs[0])
    assert log.count("modelci:reprofile") >= 1 and reprof == ["ranker"]
    # every bench event is registered vocabulary (ISSUE 10 satellite)
    for lg in (log, gw_h.log):
        assert not unregistered(lg), unregistered(lg)

    bench["scenarios"]["drift"] = {
        "hand_p99_s": _round(r_h.p99, 6),
        "profile_p99_s": _round(r_p.p99, 6),
        "p99_ratio": round(ratio, 4),
        "profiles_committed": log.count("modelci:profile"),
        "profile": {"cloud": worst.cloud, "key": worst.key,
                    "service_time_s": round(worst.service_time_s, 9),
                    "source": worst.source},
        "planned": rec.outputs["deploy"],
        "injected_factor": factor,
        "drift": {"firing": len(drifts),
                  "ratio": drifts[0]["ratio"],
                  "first_drift_seq": drifts[0]["seq"],
                  "first_migrate_seq": migs[0]["seq"],
                  "migrates_profile_drift": len(migs),
                  "reprofile_armed": reprof},
        "scrapes": log.count("metrics:scrape")}
    return [{
        "name": "gateway_drift_race",
        "us_per_call": r_p.p99 * 1e6,
        "derived": f"p99_ratio={ratio:.4f};"
                   f"profiles={log.count('modelci:profile')};"
                   f"drift_firing={len(drifts)};"
                   f"drift_seq={drifts[0]['seq']};"
                   f"migrate_seq={migs[0]['seq']};"
                   f"injected_factor={factor}",
    }]


# -- contention tier (ISSUE 9): one CapacityMarket under both planes --------

def _contention_pipeline():
    """The training side of the contention race: a prep -> 4-branch tune
    fan-out -> select -> train DAG with fixed sim_s durations (analytic,
    so the makespan ratio is host-independent; the serving side keeps its
    measured Predictor)."""
    fns = {"prep": lambda: 1.0,
           "tune": lambda i, p: {"lr": 0.01 * (1 + i), "loss": 1.0 / (1 + i)},
           "select": lambda *rs: min(rs, key=lambda r: r["loss"]),
           "train": lambda p, best: {"loss": best["loss"] / 2}}
    pipe = Pipeline("contend-tune")
    prep = pipe.step(fns["prep"], name="prep", cache=False)
    branches = [pipe.step(fns["tune"], i, prep, name=f"tune{i}", cache=False)
                for i in range(4)]
    best = pipe.step(fns["select"], *branches, name="select", cache=False)
    pipe.step(fns["train"], prep, best, name="train", cache=False)
    spec = pipe.compile()
    sims = {"prep": 0.3, "select": 0.05, "train": 1.5,
            **{f"tune{i}": 1.2 for i in range(4)}}
    for s in spec.steps:
        s.sim_s = sims[s.name]
    return spec


def _contention_scenario(pred: Predictor, bench: dict) -> list[dict]:
    """ISSUE 9 acceptance: training and serving colocated on ONE 4-slot
    gcp CapacityMarket, raced four ways.

      dedicated     the serving burst alone, no market -- the baseline the
                    1.3x p99 gate compares against;
      priority_on   training leases recorded first, then the same burst:
                    elastic scale-ups preempt the youngest recorded
                    training lease (spot semantics), so serving is never
                    denied -- p99 must stay within 1.3x dedicated;
      priority_off  same layout, serving_priority=False: the contended
                    scale-up is DENIED (gateway:scale_denied capacity),
                    zero preempts -- the counterfactual that shows the
                    priority class doing the work;
      training      the mirror image on a fresh market: the serving burst
                    recorded first, then the orchestrator runs through the
                    recorded rise-edges -- its youngest attempt is killed
                    at the over-committing edge, re-enters RetryPolicy
                    backoff (exactly-once asserted), and the contended
                    makespan must stay <= 2x the uncontended run.  The
                    budget planner reserves serving headroom on this leg
                    (plan_budget), so training also waits rather than
                    crowding the reserve.

    Every leg ends with ``check_conservation()``: no cloud's committed
    lease timeline ever exceeds its slots."""
    slots = 4
    t8 = pred.service_time(8)
    prof = get_profile("gcp")
    per_batch = prof.network_rtt_s + prof.lb_overhead_s + t8
    n = 480
    rate = 3.0 * 8 / per_batch           # 3x a single replica's ceiling

    def run_serving(market):
        log = EventLog()
        gw = Gateway(log=log, shared_capacity=market)
        gw.deploy("m", pred, prof,
                  autoscaler=AutoscalerConfig(min_replicas=1,
                                              max_replicas=slots,
                                              target_queue=8,
                                              scale_up_delay_s=0.01,
                                              idle_window_s=np.inf),
                  max_batch=8)
        out = gw.run([TrafficSpec("m", n, arrival="poisson", rate=rate)],
                     seed=0)
        denied = sum(1 for e in log.named("gateway:scale_denied")
                     if e["reason"] == "capacity")
        return out, log, denied

    def run_training(market, log=None):
        orch = Orchestrator({"gcp": 3}, policy="makespan",
                            log=log or EventLog(),
                            retry=RetryPolicy(max_retries=3, backoff_s=0.3),
                            shared_capacity=market)
        return orch.execute(_contention_pipeline()), orch

    # dedicated baseline: the burst with the cluster to itself
    out_d, _, _ = run_serving(None)
    p99_d = out_d.per_model["m"].p99

    # priority on: recorded training, then the burst preempts its way up
    mkt_on = CapacityMarket({"gcp": slots})
    run_training(mkt_on)
    out_on, log_on, denied_on = run_serving(mkt_on)
    mkt_on.check_conservation()
    p99_on = out_on.per_model["m"].p99
    preempts_on = log_on.count("capacity:preempt")

    # priority off: the same layout must deny the contended scale-up
    mkt_off = CapacityMarket({"gcp": slots}, serving_priority=False)
    run_training(mkt_off)
    out_off, log_off, denied_off = run_serving(mkt_off)
    mkt_off.check_conservation()
    p99_off = out_off.per_model["m"].p99

    # training leg: serving recorded first, orchestrator rides the edges
    mkt_tr = CapacityMarket({"gcp": slots})
    budget = mkt_tr.plan_budget({"gcp": 1.0}, work_s=0.3 + 4 * 1.2 + 1.55)
    run_serving(mkt_tr)
    tr_log = EventLog()
    rec_c, _ = run_training(mkt_tr, log=tr_log)
    mkt_tr.check_conservation()
    rec_u, _ = run_training(None)        # uncontended makespan baseline
    exactly_once = all(
        r.status == "done"
        and sum(1 for a in r.attempts if a["status"] == "ok") == 1
        and all(a["status"] in ("ok", "outage", "preempted", "cancelled")
                for a in r.attempts)
        for r in rec_c.steps.values())
    mk_ratio = rec_c.makespan_s / rec_u.makespan_s
    p99_ratio = p99_on / p99_d

    print(f"contention (4-slot gcp market, burst {n} reqs @ 3x one-replica "
          "ceiling vs the tune fan-out):", file=sys.stderr)
    print(f"  serving p99: dedicated {p99_d:.5f}s | priority-on {p99_on:.5f}s"
          f" ({p99_ratio:.2f}x, {preempts_on} preempts) | priority-off "
          f"{p99_off:.5f}s ({denied_off} denied)", file=sys.stderr)
    print(f"  training makespan: uncontended {rec_u.makespan_s:.2f}s | "
          f"contended {rec_c.makespan_s:.2f}s ({mk_ratio:.2f}x, "
          f"{tr_log.count('capacity:preempt')} preempts, reserve "
          f"{budget['reserve']})", file=sys.stderr)

    # acceptance: priority keeps serving whole (preempt, never deny) within
    # 1.3x dedicated; no-priority shows the denial; preempted training
    # stays exactly-once and <= 2x uncontended
    assert preempts_on >= 1 and denied_on == 0, (preempts_on, denied_on)
    assert denied_off >= 1 and log_off.count("capacity:preempt") == 0
    assert p99_ratio <= 1.3, (p99_on, p99_d)
    assert rec_c.status == "succeeded" and exactly_once
    assert mk_ratio <= 2.0, (rec_c.makespan_s, rec_u.makespan_s)

    bench["scenarios"]["contention"] = {
        "slots": slots,
        "dedicated": {"p99_s": _round(p99_d, 6)},
        "priority_on": {"p99_s": _round(p99_on, 6),
                        "preempts": preempts_on,
                        "leases": log_on.count("capacity:lease"),
                        "scale_denied": denied_on,
                        "sim_cost_usd": round(out_on.total_cost_usd, 8)},
        "priority_off": {"p99_s": _round(p99_off, 6),
                         "preempts": log_off.count("capacity:preempt"),
                         "scale_denied": denied_off},
        "training": {"contended_makespan_s": round(rec_c.makespan_s, 4),
                     "uncontended_makespan_s": round(rec_u.makespan_s, 4),
                     "preempts": tr_log.count("capacity:preempt"),
                     "retries": tr_log.count("pipeline:retry"),
                     "exactly_once": exactly_once,
                     "budget": {"reserve": budget["reserve"],
                                "training_slots": budget["training_slots"],
                                "est_makespan_s":
                                    round(budget["est_makespan_s"], 4)}},
        "p99_ratio": round(p99_ratio, 4),
        "makespan_ratio": round(mk_ratio, 4)}
    return [{
        "name": "gateway_contention_race",
        "us_per_call": p99_on * 1e6,
        "derived": f"p99_ratio={p99_ratio:.3f};"
                   f"makespan_ratio={mk_ratio:.3f};"
                   f"preempts_on={preempts_on};denied_off={denied_off};"
                   f"training_preempts={tr_log.count('capacity:preempt')};"
                   f"exactly_once={exactly_once}",
    }]


# -- scale tier (ISSUE 7): simulator throughput, not model latency ----------

# bench-local fifth cloud so the fleet spans five providers without
# touching the repo-wide PROFILES registry (tests pin its exact key set):
# an on-prem Kubeflow analog -- LAN RTT, no LB hop, free egress, mid price
_ONPREM = CloudProfile("onprem", TPU_V5E, (4, 4),
                       network_rtt_s=0.0008, lb_overhead_s=0.0,
                       model_load_s=0.25, startup_s=0.5,
                       cost_per_s=0.95 / 3600.0,
                       egress_per_gb=0.0, interconnect_bw=0.625e9)
SCALE_CLOUDS = ("gcp", "ibm", "baremetal", "k8s", "onprem")
SCALE_MODELS = 12
SCALE_BATCH = 2048


class _SimBackend:
    """Analytic backend for the scale tier.  The tier measures SIMULATOR
    throughput (events/sec through the engine), so the compute term must
    be O(1) per batch and identical on every host -- a jitted predict
    here would benchmark the accelerator, not the event loop.  The
    latency/dollar scenarios above keep their measured Predictors."""

    def __init__(self, name: str, base_s: float, per_req_s: float):
        self.name = name
        self.base_s = base_s
        self.per_req_s = per_req_s

    def service_time(self, b: int) -> float:
        return self.base_s + self.per_req_s * b


def _build_scale_fleet(n_per_model: int, seed: int = 0):
    """A dozen single-cloud models over five clouds, every pool pinned at
    two replicas and offered ~1.3x its ceiling (sustained overload is the
    regime the vector engine must win: queues never drain, so whole
    arrival spans fold between batch completions).  Model 0 carries a
    standby and takes a mid-run outage on its primary cloud, so the
    failover/recover control path runs inside the measured loop.  All
    classes are non-preempting ("standard" / "batch") -- preemption would
    pin the engines to per-arrival stepping and belongs to the latency
    scenarios above, not the throughput tier."""
    profs = {c: get_profile(c) for c in SCALE_CLOUDS if c != "onprem"}
    profs["onprem"] = _ONPREM
    gw = Gateway(log=EventLog())
    traffic = []
    outage_cloud = SCALE_CLOUDS[0]
    window_s = 0.0
    for i in range(SCALE_MODELS):
        cloud = SCALE_CLOUDS[i % len(SCALE_CLOUDS)]
        prof = profs[cloud]
        backend = _SimBackend(f"scale{i}", 2e-3, 2e-5)
        per_batch = (prof.network_rtt_s + prof.lb_overhead_s
                     + backend.service_time(SCALE_BATCH))
        cap_rps = 2 * SCALE_BATCH / per_batch        # 2-replica ceiling
        # every model on the outage cloud carries a standby: a pool-less
        # model logs scale_denied per TIMESTEP, which is exactly the
        # regime the vector engine cannot (and must not) skip -- the
        # throughput tier measures failover, not blackholed traffic
        gw.deploy(f"scale{i}", backend, prof,
                  standby=(profs[SCALE_CLOUDS[(i + 1) % len(SCALE_CLOUDS)]]
                           if cloud == outage_cloud else None),
                  autoscaler=AutoscalerConfig(min_replicas=2,
                                              max_replicas=2,
                                              idle_window_s=np.inf),
                  max_batch=SCALE_BATCH)
        rate = 1.3 * cap_rps
        window_s = max(window_s, n_per_model / rate)
        # two non-preempting streams per model: distinct per-class queues
        # exercise the grouped bulk-append path, not just one extend
        traffic.append(TrafficSpec(f"scale{i}", (2 * n_per_model) // 3,
                                   arrival="poisson", rate=rate * 2 / 3,
                                   slo="standard"))
        traffic.append(TrafficSpec(f"scale{i}", n_per_model // 3,
                                   arrival="poisson", rate=rate / 3,
                                   slo="batch"))
    failures = [FailureSpec(outage_cloud, at_s=0.35 * window_s,
                            duration_s=0.2 * window_s)]
    return gw, traffic, failures


def _run_scale(n_per_model: int, engine: str, seed: int = 0):
    gw, traffic, failures = _build_scale_fleet(n_per_model, seed)
    out = gw.run(traffic, seed=seed, failures=failures, engine=engine)
    return gw, out


def _scale_scenario(bench: dict, *, smoke: bool = False) -> list[dict]:
    """ISSUE 7 acceptance: >=10^6 requests end-to-end through the gateway
    with events/sec recorded, the vectorized engine >=50x the scalar
    per-request loop on the same scenario (>=10x on the reduced CI smoke
    cut), gated by the bit-compatibility oracle on a small seed."""
    # oracle leg: the engines must agree EXACTLY before speed means
    # anything (the hypothesis suite covers the wide scenario space;
    # this pins the bench's own fleet shape, outage included)
    n_oracle = 400
    gw_s, out_s = _run_scale(n_oracle, "scalar")
    gw_v, out_v = _run_scale(n_oracle, "vector")
    assert gw_s.log.dump() == gw_v.log.dump(), \
        "scale oracle: EventLog diverged between engines"
    assert {m: r.summary() for m, r in out_s.per_model.items()} \
        == {m: r.summary() for m, r in out_v.per_model.items()}
    assert out_s.costs == out_v.costs and out_s.makespan_s == out_v.makespan_s
    n_oracle_total = gw_v.run_stats["requests"]

    n_per_model = 14_000 if smoke else 90_000
    min_speedup = 10.0 if smoke else 50.0
    gw_sc, out_sc = _run_scale(n_per_model, "scalar")
    gw_vec, out_vec = _run_scale(n_per_model, "vector")
    sc, vec = gw_sc.run_stats, gw_vec.run_stats
    # same scenario, same outcome -- the speed claim is apples-to-apples
    assert {m: r.summary() for m, r in out_sc.per_model.items()} \
        == {m: r.summary() for m, r in out_vec.per_model.items()}
    speedup = sc["wall_s"] / vec["wall_s"]

    print(f"scale tier: {vec['requests']} requests / {SCALE_MODELS} models "
          f"/ {len(SCALE_CLOUDS)} clouds", file=sys.stderr)
    print(f"  scalar {sc['wall_s']:.2f}s "
          f"({sc['events_per_s']:,.0f} ev/s, "
          f"{sc['requests_per_s']:,.0f} req/s)", file=sys.stderr)
    print(f"  vector {vec['wall_s']:.2f}s "
          f"({vec['events_per_s']:,.0f} ev/s, "
          f"{vec['requests_per_s']:,.0f} req/s)  ->  "
          f"{speedup:.1f}x", file=sys.stderr)

    # acceptance: the vectorized engine clears the asserted floor
    assert speedup >= min_speedup, \
        f"scale speedup {speedup:.1f}x < {min_speedup}x"

    def side(stats):
        return {"wall_s": round(stats["wall_s"], 4),
                "sim_events": stats["sim_events"],
                "events_per_s": round(stats["events_per_s"], 1),
                "requests_per_s": round(stats["requests_per_s"], 1)}

    bench["scenarios"]["scale"] = {
        "requests": vec["requests"],
        "models": SCALE_MODELS,
        "clouds": len(SCALE_CLOUDS),
        "oracle_requests": n_oracle_total,
        "scalar": side(sc),
        "vector": side(vec),
        "speedup": round(speedup, 2),
        "asserted_min_speedup": min_speedup,
        "shed": sum(r.shed_total for r in out_vec.per_model.values()),
        "failovers": gw_vec.log.count("gateway:failover"),
        "sim_cost_usd": round(out_vec.total_cost_usd, 8)}
    return [{
        "name": "gateway_scale_vector",
        "us_per_call": 1e6 / vec["requests_per_s"],
        "derived": f"requests={vec['requests']};speedup={speedup:.1f}x;"
                   f"events_per_s={vec['events_per_s']:.0f};"
                   f"requests_per_s={vec['requests_per_s']:.0f};"
                   f"scalar_wall_s={sc['wall_s']:.3f};"
                   f"vector_wall_s={vec['wall_s']:.3f}",
    }]


def smoke() -> None:
    """CI bench-smoke: run the overload scenario (with its burn-rate
    telemetry leg), the instrumentation-overhead race, the contention
    race (ISSUE 9: training + serving burst through one CapacityMarket,
    priority on vs off), the model-CI drift tier (ISSUE 10:
    profile-planned placement + injected shift -> profile:drift before
    the profile_drift migrate) and the reduced scale tier (engine oracle +
    >=10x vector-over-scalar on a smaller request count), then validate both the freshly produced record and
    (when present) the committed BENCH_gateway.json against the schema
    -- including the shed-rate fields, the alert-before-migrate
    ordering, the <10% overhead gate, the drift seq ordering, the
    contention ratios and the recorded scale speedup."""
    pred = _make_predictor("small", WIDTHS["small"])
    bench: dict = {"schema": BENCH_SCHEMA, "scenarios": {}}
    _overload_shed_scenario(pred, bench)
    _observability_scenario(pred, bench)
    _drift_scenario(pred, bench)
    _contention_scenario(pred, bench)
    _scale_scenario(bench, smoke=True)
    validate_bench(bench, require=("overload", "observability", "drift",
                                   "contention", "scale"))
    if BENCH_JSON.exists():
        validate_bench(json.loads(BENCH_JSON.read_text()),
                       require=("fleet", "slo_failover", "split_cost",
                                "overload", "observability", "drift",
                                "contention", "scale"))
        print(f"validated {BENCH_JSON}", file=sys.stderr)
    print("overload race:",
          json.dumps(bench["scenarios"]["overload"]["race"]),
          file=sys.stderr)
    ct = bench["scenarios"]["contention"]
    print("contention:", json.dumps({"p99_ratio": ct["p99_ratio"],
                                     "makespan_ratio": ct["makespan_ratio"]}),
          file=sys.stderr)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="overload scenario + schema validation only (CI)")
    if ap.parse_args().smoke:
        smoke()
    else:
        print("name,us_per_call,derived")
        for r in run():
            print(f"{r['name']},{r['us_per_call']:.3f},{r['derived']}")
