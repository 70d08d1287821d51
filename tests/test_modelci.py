"""Model-CI profiling plane units (ISSUE 10): the ModelProfile artifact
schema, the ProfileStore over the shared ArtifactCache, the orchestrator's
``kind="profile"`` commit path, the DeploySpec.profile-planned placement,
and the DriftMonitor's profile-vs-observed controller loop.

The end-to-end acceptance -- profile-planned p99 racing a hand-tuned
plan, an injected service-time shift firing ``profile:drift`` strictly
before the ``reason=profile_drift`` migrate -- lives in
``benchmarks/bench_gateway.py`` (the drift tier); this file pins the
component contracts.
"""
import pytest

from repro.clouds.profiles import get_profile
from repro.core.pipeline import Pipeline
from repro.modelci import (ModelProfile, ProfiledBackend, ProfileSpec,
                           ProfileStore, finalize, measure, roofline_fields)
from repro.pipelines import (ArtifactCache, DeploySpec, Orchestrator,
                             PipelineRuns)
from repro.serving.gateway import AutoscalerConfig, CloudCapacity, Gateway
from repro.telemetry.drift import DriftConfig, DriftMonitor
from repro.telemetry.events import EventLog
from repro.telemetry.metrics import MetricsRegistry


class FakeBackend:
    """Linear cost model: service_time(b) = b * per_request."""

    def __init__(self, name="m", per_request=0.01):
        self.name = name
        self.per_request = per_request

    def service_time(self, b: int) -> float:
        return b * self.per_request


class FakeDisaggBackend(FakeBackend):
    def prefill_time(self) -> float:
        return 0.006

    def decode_time(self) -> float:
        return 0.004


# -- ModelProfile -------------------------------------------------------------

def test_profile_validation_and_effective_service():
    with pytest.raises(ValueError):
        ModelProfile("m", "gcp", 0.0)
    with pytest.raises(ValueError):
        ModelProfile("m", "gcp", float("inf"))
    with pytest.raises(ValueError):                  # one-sided split
        ModelProfile("m", "gcp", 0.01, prefill_s=0.006)
    p = ModelProfile("m", "gcp", 0.01)
    assert p.effective_service_s == 0.01
    d = ModelProfile("m", "gcp", 0.01, prefill_s=0.006, decode_s=0.005)
    assert d.effective_service_s == pytest.approx(0.011)


def test_profile_key_is_content_hash():
    a = ModelProfile("m", "gcp", 0.01, max_batch=8)
    b = ModelProfile("m", "gcp", 0.01, max_batch=8)
    assert a.key == b.key                            # identical -> dedupe
    assert a.key.startswith("profile_")
    c = ModelProfile("m", "gcp", 0.0100001, max_batch=8)
    assert c.key != a.key                            # any change re-keys
    assert ModelProfile("m", "ibm", 0.01, max_batch=8).key != a.key


def test_profile_dict_round_trip():
    p = ModelProfile("m", "aws", 0.02, max_batch=4, prefill_s=0.015,
                     decode_s=0.005, memory_bytes=123, load_s=9.0,
                     roofline={"compute_s": 1.0}, source="measured")
    assert ModelProfile.from_dict(p.to_dict()) == p
    assert ModelProfile.from_dict(p.to_dict()).key == p.key


def test_profile_demand_bridge():
    p = ModelProfile("m", "gcp", 0.01, prefill_s=0.015, decode_s=0.005)
    with pytest.raises(ValueError):
        p.demand()                                   # exactly one of
    with pytest.raises(ValueError):
        p.demand(rate=1.0, load_erlangs=1.0)
    dem = p.demand(load_erlangs=2.0)
    assert dem.name == "m" and dem.service_time_s == 0.01
    assert dem.rate == pytest.approx(2.0 / 0.02)     # effective (pf+dc)
    assert dem.prefill_s == 0.015 and dem.decode_s == 0.005
    assert p.demand(rate=7.0).rate == 7.0


# -- measurement --------------------------------------------------------------

def test_measure_blended_and_disagg_fields():
    fields = measure(FakeBackend(per_request=0.01), max_batch=8)
    assert fields["service_time_s"] == pytest.approx(0.01)
    assert fields["max_batch"] == 8 and fields["source"] == "measured"
    assert "prefill_s" not in fields                 # no two-point model
    d = measure(FakeDisaggBackend(), max_batch=8,
                weights={"w": [1.0, 2.0]})
    assert d["prefill_s"] == 0.006 and d["decode_s"] == 0.004
    assert d["memory_bytes"] > 0


def test_roofline_fields_closed_form():
    from repro.configs.registry import get_config
    cfg = get_config("gemma3_4b")
    fields = roofline_fields(cfg)
    assert fields["source"] == "roofline"
    assert fields["service_time_s"] > 0
    assert fields["memory_bytes"] == 2 * cfg.approx_active_params()
    assert fields["roofline"]["memory_s"] > 0        # decode: bandwidth-bound


def test_finalize_stamps_cloud_constants():
    gcp = get_profile("gcp")
    mp = finalize(measure(FakeBackend(), max_batch=4), "m", gcp)
    assert mp.cloud == "gcp" and mp.load_s == gcp.model_load_s
    assert mp.source == "measured"


# -- ProfileStore -------------------------------------------------------------

def test_store_put_get_latest_and_dedupe():
    store = ProfileStore()
    a = ModelProfile("m", "gcp", 0.01)
    e1 = store.put(a)
    e2 = store.put(ModelProfile("m", "gcp", 0.01))   # identical: dedupe
    assert e1 is e2
    assert store.get("m", "gcp") == a
    newer = ModelProfile("m", "gcp", 0.02)
    store.put(newer)
    assert store.get("m", "gcp") == newer            # latest supersedes
    assert store.cache.get(a.key) is not None        # history survives
    assert store.get("m", "aws") is None
    store.put(ModelProfile("m", "ibm", 0.03))
    store.put(ModelProfile("other", "gcp", 0.5))
    assert store.clouds("m") == ["gcp", "ibm"]
    assert store.models() == ["m", "other"]


def test_store_worst_and_demand():
    store = ProfileStore()
    store.put(ModelProfile("m", "gcp", 0.01))
    store.put(ModelProfile("m", "ibm", 0.03))
    assert store.worst("m").cloud == "ibm"           # conservative pick
    assert store.worst("m", ["gcp"]).cloud == "gcp"  # restricted to plan
    dem = store.demand("m", load_erlangs=3.0)
    assert dem.service_time_s == 0.03
    with pytest.raises(KeyError):
        store.worst("m", ["aws"])                    # no artifact there
    with pytest.raises(KeyError):
        store.worst("ghost")


def test_store_pull_prices_residency_move():
    store = ProfileStore()
    p = ModelProfile("m", "gcp", 0.01, memory_bytes=10**9)
    store.put(p)
    entry, t_s, usd = store.pull("m", "gcp", get_profile("gcp"))
    assert t_s == 0.0 and usd == 0.0                 # already resident
    entry, t_s, usd = store.pull("m", "gcp", get_profile("ibm"))
    assert t_s > 0 and usd >= 0                      # priced by best_transfer
    assert "ibm" in entry.clouds                     # residency committed
    _, t2, u2 = store.pull("m", "gcp", get_profile("ibm"))
    assert t2 == 0.0 and u2 == 0.0                   # second pull is local
    with pytest.raises(KeyError):
        store.pull("m", "aws", get_profile("gcp"))


# -- ProfiledBackend ----------------------------------------------------------

def test_profiled_backend_cost_model_is_the_artifact():
    p = ModelProfile("m", "gcp", 0.01, max_batch=8)
    be = ProfiledBackend(p)
    assert be.name == "m"
    assert be.service_time(4) == pytest.approx(0.04)
    assert be.service_time(0) == pytest.approx(0.01)  # floor at one request
    assert not hasattr(be, "prefill_time")            # no split, no attrs
    split = ProfiledBackend(ModelProfile("m", "gcp", 0.01,
                                         prefill_s=0.006, decode_s=0.004))
    assert split.prefill_time() == 0.006 and split.decode_time() == 0.004


# -- orchestrator profile steps ----------------------------------------------

def _profile_pipeline(store, backend, clouds=("gcp", "ibm")):
    pipe = Pipeline("ci")
    for c in clouds:
        pipe.step(lambda: measure(backend, max_batch=8),
                  name=f"profile_{c}", kind="profile", pin=c,
                  payload=ProfileSpec("m", store, max_batch=8))
    return pipe


def test_profile_step_commits_per_cloud_artifacts():
    store, log = ProfileStore(), EventLog()
    orch = Orchestrator({"gcp": 1, "ibm": 1}, log=log)
    rec = orch.execute(_profile_pipeline(store, FakeBackend()).compile())
    assert rec.status == "succeeded"
    assert store.clouds("m") == ["gcp", "ibm"]
    # the cloud constant differentiates the artifacts per cloud
    assert store.get("m", "gcp").load_s == get_profile("gcp").model_load_s
    evs = log.named("modelci:profile")
    assert [e["cloud"] for e in evs] == ["gcp", "ibm"]
    assert all(e["key"].startswith("profile_") for e in evs)


def test_profile_step_requires_spec_payload():
    pipe = Pipeline("ci")
    pipe.step(lambda: {}, name="p", kind="profile")
    with pytest.raises(ValueError, match="ProfileSpec"):
        Orchestrator({"gcp": 1}).execute(pipe.compile())
    bad = Pipeline("ci2")
    with pytest.raises(ValueError):
        ProfileSpec("", ProfileStore())              # model must be named
    with pytest.raises(ValueError):
        ProfileSpec("m", store=object())             # store must store
    bad.step(lambda: {}, name="p", kind="profile", payload=object())
    with pytest.raises(ValueError, match="ProfileSpec"):
        Orchestrator({"gcp": 1}).execute(bad.compile())


def test_cached_recurring_profile_still_refreshes_store():
    """The second recurring firing hits the step cache, but the commit
    hook must still run: a fresh store (new process, same ArtifactStore)
    learns the latest pointers from cached completions."""
    cache = ArtifactCache()
    store = ProfileStore(cache)
    log = EventLog()
    orch = Orchestrator({"gcp": 1, "ibm": 1}, cache=cache, log=log)
    spec = _profile_pipeline(store, FakeBackend()).compile()
    recs = PipelineRuns(orch).recurring(spec, every_s=60.0, runs=2)
    assert recs[1].cache_hits == 2                   # measurements cached
    assert log.count("modelci:profile") == 4         # committed every firing
    assert store.clouds("m") == ["gcp", "ibm"]


# -- DeploySpec.profile placement ---------------------------------------------

def _deploy_spec(store):
    return DeploySpec(
        "m",
        clouds=[CloudCapacity(get_profile("gcp"), 2, 1.0),
                CloudCapacity(get_profile("ibm"), 2, 1.4)],
        load_erlangs=2.0, objective="p99", split=True,
        autoscaler=AutoscalerConfig(min_replicas=3, max_replicas=4,
                                    target_queue=8),
        max_batch=8, profile=store)


def test_profile_planned_deploy_uses_store_demand():
    store, log = ProfileStore(), EventLog()
    backend = FakeBackend(per_request=0.01)
    pipe = _profile_pipeline(store, backend)
    pipe.step(lambda: backend, name="deploy", kind="deploy",
              payload=_deploy_spec(store))
    gw = Gateway(log=log)
    rec = Orchestrator({"gcp": 1, "ibm": 1}, log=log).execute(
        pipe.compile(), gateway=gw)
    assert rec.status == "succeeded"
    out = rec.outputs["deploy"]
    assert out["profiled"] is True
    assert len(out["replicas"]) == 2                 # genuinely split
    assert "m" in gw.deployments
    # the gateway's drift monitor knows the planned-from artifact only
    # when drift detection is configured; bare gateways just deploy
    assert gw.drift is None


def test_profile_planned_deploy_infeasible_without_artifacts():
    """No committed profiles for the model on the candidate clouds is an
    infeasible deploy, not a silent fall back to hand-measured numbers."""
    store, log = ProfileStore(), EventLog()
    store.put(ModelProfile("other", "gcp", 0.01))    # wrong model
    pipe = Pipeline("ci")
    pipe.step(lambda: FakeBackend(), name="deploy", kind="deploy",
              payload=_deploy_spec(store))
    rec = Orchestrator({"gcp": 1, "ibm": 1}, log=log).execute(
        pipe.compile(), gateway=Gateway(log=log))
    assert rec.status == "failed"
    assert rec.steps["deploy"].status == "failed"
    assert rec.steps["deploy"].attempts[-1]["status"] == "infeasible"


# -- DriftMonitor -------------------------------------------------------------

def test_drift_config_validation():
    for bad in (dict(threshold=1.0), dict(threshold=0.5),
                dict(sustain=0), dict(min_n=0)):
        with pytest.raises(ValueError):
            DriftConfig(**bad)


def _fed_monitor(threshold=1.5, sustain=2, min_n=8, metrics=None):
    log = EventLog()
    mon = DriftMonitor(DriftConfig(threshold=threshold, sustain=sustain,
                                   min_n=min_n), log=log, metrics=metrics)
    mon.watch("m", ModelProfile("m", "gcp", 0.01), t=0.0)
    return mon, log


def feed(mon, t, ratio, n=10, _state={}):
    """One scrape's cumulative counters at observed ratio x profile.
    Keyed by the monitor itself, which the dict keeps alive: an id() key
    was reused by a later test's monitor and handed it stale counters."""
    key = mon
    busy, served = _state.get(key, (0.0, 0))
    busy += ratio * 0.01 * n
    served += n
    _state[key] = (busy, served)
    mon.observe(t, "m", busy, served)


def test_drift_fires_on_sustained_out_of_band_only():
    mon, log = _fed_monitor()
    feed(mon, 1.0, ratio=1.0)
    feed(mon, 2.0, ratio=2.0)                        # 1st out-of-band
    assert not mon.is_drifting("m")                  # sustain=2
    feed(mon, 3.0, ratio=2.0)                        # 2nd: fires
    assert mon.is_drifting("m") and mon.drifting_models() == {"m"}
    evs = log.named("profile:drift")
    assert len(evs) == 1 and evs[0]["state"] == "firing"
    assert evs[0]["ratio"] == pytest.approx(2.0, abs=1e-3)
    assert mon.pop_reprofile() == {"m"}
    assert mon.pop_reprofile() == set()              # drained: armed once
    feed(mon, 4.0, ratio=2.0)                        # still firing: one edge
    assert len(log.named("profile:drift")) == 1
    assert log.count("modelci:reprofile") == 1
    feed(mon, 5.0, ratio=1.0)                        # back in band
    assert not mon.is_drifting("m")
    assert [e["state"] for e in log.named("profile:drift")] \
        == ["firing", "resolved"]


def test_drift_detects_too_fast_too():
    """A placement planned from an inflated profile over-provisions: the
    band is two-sided, ratio <= 1/threshold drifts as well."""
    mon, log = _fed_monitor(threshold=1.5)
    feed(mon, 1.0, ratio=0.5)
    feed(mon, 2.0, ratio=0.5)
    assert mon.is_drifting("m")


def test_drift_small_intervals_are_not_evidence():
    """A scrape with fewer than min_n served requests neither advances
    nor resets the streak -- quiet intervals must not mask real drift."""
    mon, log = _fed_monitor(min_n=8)
    feed(mon, 1.0, ratio=2.0, n=10)                  # streak 1
    feed(mon, 2.0, ratio=2.0, n=3)                   # below min_n: ignored
    assert not mon.is_drifting("m")
    feed(mon, 3.0, ratio=2.0, n=10)                  # streak 2: fires
    assert mon.is_drifting("m")


def test_drift_metrics_and_staleness():
    reg = MetricsRegistry()
    mon, log = _fed_monitor(metrics=reg)
    feed(mon, 7.0, ratio=2.0)
    assert reg.value("modelci_profile_staleness", model="m") == 7.0
    assert reg.value("modelci_drift_ratio", model="m") \
        == pytest.approx(2.0, abs=1e-3)
    feed(mon, 8.0, ratio=2.0)
    assert reg.total("modelci_drift_total", model="m") == 1


def test_drift_rewatch_and_reset_semantics():
    mon, log = _fed_monitor()
    feed(mon, 1.0, ratio=2.0)
    feed(mon, 2.0, ratio=2.0)
    assert mon.is_drifting("m")
    # re-watch (re-deploy after re-profile): drift state clears
    mon.watch("m", ModelProfile("m", "gcp", 0.02), t=2.0)
    assert not mon.is_drifting("m") and mon.reprofile == set()
    # reset (between gateway runs): baselines restart, watches survive
    mon.reset()
    assert not mon.active
    mon.observe(3.0, "ghost", 1.0, 100)              # unwatched: ignored
    assert not mon.drifting_models()


def test_gateway_drift_requires_scrape_clock():
    with pytest.raises(ValueError, match="scrape_every_s"):
        Gateway(drift=DriftConfig())
    with pytest.raises(ValueError, match="scrape_every_s"):
        Gateway(drift=DriftConfig(), metrics=MetricsRegistry())
    gw = Gateway(drift=DriftConfig(), metrics=MetricsRegistry(),
                 scrape_every_s=0.5)
    assert gw.drift is not None
    gw.deploy("m", FakeBackend(), get_profile("gcp"),
              planned_from=ModelProfile("m", "gcp", 0.01))
    assert "m" in gw.drift._watch
