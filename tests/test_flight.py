"""Flight recorder, anomaly detectors, and incident bundles (DESIGN.md
§14): the always-on black box, the step-boundary detector sweep, atomic
bundle capture, and the postmortem report.

The load-bearing acceptance property: under a seeded single-fault run,
each injected fault class (exception, nan/poison corruption, crash)
yields EXACTLY ONE bundle whose trigger names the correct detector and —
when the fault is attributable — the faulted uid; a clean seeded run of
equal length yields ZERO bundles (the incident dir is never created).
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.engine import (Engine, EngineConfig, FaultInjector, FaultSpec,
                          InjectedCrash)
from repro.models import get_model
from repro.obs import (AnomalyDetector, DETECTORS, FlightRecorder,
                       atomic_dir, atomic_write_text,
                       load_incident_bundle, tail_lines,
                       write_incident_bundle)
from repro.launch.incident_report import main as report_main

KEY = jax.random.PRNGKey(0)
MAX_LEN = 48


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("stablelm-1.6b").reduced()
    model = get_model(cfg)
    params = model.init(KEY, cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 14)))
               for _ in range(5)]
    return cfg, model, params, prompts


class FakeClock:
    def __init__(self, tick=0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ====================================================== flight recorder
def test_flight_ring_drops_oldest_and_counts():
    fr = FlightRecorder(capacity=4, clock=FakeClock())
    for i in range(7):
        rec = fr.record(step=i, step_s=0.01)
        assert rec["step"] == i and "ts" in rec
    assert len(fr.records) == 4 and fr.dropped == 3
    assert [r["step"] for r in fr.window()] == [3, 4, 5, 6]
    hdr = fr.header()
    assert hdr["recorded"] == 7 and hdr["dropped"] == 3
    assert hdr["capacity"] == 4
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_tail_lines(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with open(p, "w") as f:
        for i in range(10):
            f.write(f"line{i}\n")
    assert tail_lines(p, 3) == ["line7", "line8", "line9"]
    assert tail_lines(str(tmp_path / "absent.jsonl")) == []


# ==================================================== anomaly detectors
def test_latency_spike_warmup_and_cooldown():
    det = AnomalyDetector(cooldown_steps=5, warmup_steps=3,
                          latency_factor=6.0)
    # warmup: a huge first step feeds the baseline, never fires
    assert det.sweep({"step": 0, "step_s": 5.0}) == []
    for s in range(1, 4):
        assert det.sweep({"step": s, "step_s": 0.01}) == []
    # baseline has decayed toward 0.01-ish; a 6x+ spike fires once
    fired = det.sweep({"step": 4, "step_s": 50.0})
    assert [f.detector for f in fired] == ["step_latency_spike"]
    assert fired[0].step == 4 and fired[0].value == 50.0
    # inside the cooldown window: suppressed
    assert det.sweep({"step": 5, "step_s": 500.0}) == []
    # past the cooldown: fires again
    for s in range(6, 9):
        det.sweep({"step": s, "step_s": 0.01})
    fired = det.sweep({"step": 9, "step_s": 500.0})
    assert [f.detector for f in fired] == ["step_latency_spike"]
    assert det.n_fired == 2


def test_latency_spike_says_device_or_host_wait():
    """A record carrying the decode readback's wait_s gets a firing that
    splits the slow step's wall into device wait and host time."""
    det = AnomalyDetector(cooldown_steps=5, warmup_steps=2,
                          latency_factor=4.0)
    for s in range(3):
        det.sweep({"step": s, "step_s": 0.2, "wait_s": 0.19})
    fired = det.sweep({"step": 3, "step_s": 1.6, "wait_s": 0.2})
    assert [f.detector for f in fired] == ["step_latency_spike"]
    assert "0.2000s of it waiting on the device" in fired[0].reason
    assert "1.4000s on the host" in fired[0].reason


def test_flight_record_carries_readback_wait(setup):
    """Every step record has wait_s: the step's readbacks (decode tokens
    and first tokens), inside the step's wall."""
    cfg, model, params, prompts = setup
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, max_len=MAX_LEN, prefill_bucket=8))
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.drain()
    recs = eng._flight.window()
    assert recs and all("wait_s" in r for r in recs)
    assert all(0.0 <= r["wait_s"] <= r["step_s"] + 1e-6 for r in recs)
    assert any(r["wait_s"] > 0 for r in recs)


def test_derived_detectors_fire_on_their_signals():
    det = AnomalyDetector(cooldown_steps=100, warmup_steps=99,
                          queue_set_point=4)
    # rung ascent (0 -> 2) + queue runaway in one record
    fired = det.sweep({"step": 0, "rung": 2, "queue": 6})
    assert {f.detector for f in fired} == {"rung_ascent", "queue_runaway"}
    # rung descent never fires
    assert det.sweep({"step": 1, "rung": 0, "queue": 2}) == []
    # accept collapse: must arm (>= 2x floor) before a fall can fire
    assert det.sweep({"step": 2, "accept": 0.1}) == []     # never armed
    det.sweep({"step": 3, "accept": 0.9})                  # arms
    fired = det.sweep({"step": 4, "accept": 0.05})
    assert [f.detector for f in fired] == ["accept_collapse"]
    # clip spike: absolute threshold and jump-over-previous
    fired = det.sweep({"step": 5, "clip_frac": 0.8})
    assert [f.detector for f in fired] == ["kv_clip_spike"]


def test_clip_jump_fires_below_absolute_threshold():
    det = AnomalyDetector(cooldown_steps=1, clip_abs=0.5, clip_jump=0.25)
    assert det.sweep({"step": 0, "clip_frac": 0.05}) == []
    fired = det.sweep({"step": 1, "clip_frac": 0.4})   # +0.35 jump, < abs
    assert [f.detector for f in fired] == ["kv_clip_spike"]


def test_note_and_drain_event_detectors():
    det = AnomalyDetector(cooldown_steps=3)
    det.note("step_retry", reason="nan logits", uid=7)
    fired = det.sweep({"step": 0, "step_s": 0.01})
    assert [f.detector for f in fired] == ["step_retry"]
    assert fired[0].uid == 7 and fired[0].reason == "nan logits"
    # cooldown applies to posted events too
    det.note("step_retry", reason="again", uid=7)
    assert det.sweep({"step": 1, "step_s": 0.01}) == []
    # drain() admits out-of-step events without a record
    det.note("injected_crash", reason="boom", step=50)
    fired = det.drain()
    assert [f.detector for f in fired] == ["injected_crash"]
    with pytest.raises(ValueError, match="unknown detector"):
        det.note("gremlin")
    assert set(DETECTORS) >= {"step_retry", "injected_crash"}


# ====================================================== atomic protocol
def test_atomic_write_text_no_tmp_residue(tmp_path):
    p = str(tmp_path / "out.txt")
    atomic_write_text(p, "hello\n")
    assert open(p).read() == "hello\n"
    atomic_write_text(p, "replaced\n")
    assert open(p).read() == "replaced\n"
    assert os.listdir(tmp_path) == ["out.txt"]


def test_atomic_dir_rollback_on_exception(tmp_path):
    final = str(tmp_path / "bundle")
    with pytest.raises(RuntimeError):
        with atomic_dir(final) as tmp:
            open(os.path.join(tmp, "partial"), "w").write("x")
            raise RuntimeError("crash mid-dump")
    assert not os.path.exists(final) and not os.path.exists(final + ".tmp")
    with atomic_dir(final) as tmp:
        open(os.path.join(tmp, "f"), "w").write("ok")
    assert os.listdir(final) == ["f"]


# ===================================================== incident bundles
def _docs():
    return {
        "trigger.json": {"schema": 1, "step": 3, "trigger": {
            "detector": "step_retry", "step": 3, "reason": "nan",
            "uid": 1, "value": None}, "firings": [
            {"detector": "step_retry", "step": 3, "reason": "nan",
             "uid": 1, "value": None}]},
        "flight.json": {"header": {"schema": 1, "capacity": 8,
                                   "recorded": 4, "dropped": 0},
                        "records": [{"step": s, "ts": s * 0.1,
                                     "step_s": 0.01, "uids": [1]}
                                    for s in range(4)]},
        "metrics.json": {},
        "fingerprint.json": {"arch": "t"},
        "provenance.json": {},
        "requests.json": {"active": [], "queued": [], "poison_uids": []},
        "journal_tail.jsonl": [json.dumps({"kind": "header"})],
    }


def test_bundle_roundtrip_and_manifest(tmp_path):
    path = write_incident_bundle(str(tmp_path / "inc"),
                                 "incident-000-step_retry", _docs())
    assert os.path.basename(path) == "incident-000-step_retry"
    bundle = load_incident_bundle(path)
    assert bundle["MANIFEST.json"]["name"] == "incident-000-step_retry"
    assert bundle["trigger.json"]["trigger"]["detector"] == "step_retry"
    assert bundle["journal_tail.jsonl"] == [{"kind": "header"}]
    assert len(bundle["flight.json"]["records"]) == 4
    assert not os.path.exists(path + ".tmp")


@pytest.mark.parametrize("corrupt", [
    lambda p: os.remove(os.path.join(p, "MANIFEST.json")),
    lambda p: open(os.path.join(p, "MANIFEST.json"), "w").write("{nope"),
    lambda p: os.remove(os.path.join(p, "metrics.json")),
    lambda p: open(os.path.join(p, "flight.json"), "w").write("]["),
])
def test_load_bundle_rejects_corruption(tmp_path, corrupt):
    path = write_incident_bundle(str(tmp_path / "inc"),
                                 "incident-000-step_retry", _docs())
    corrupt(path)
    with pytest.raises(ValueError):
        load_incident_bundle(path)
    # and the CLI turns it into exit 1
    assert report_main([path, "--validate"]) == 1


def test_bundle_missing_required_file(tmp_path):
    docs = _docs()
    del docs["requests.json"]
    path = write_incident_bundle(str(tmp_path / "inc"),
                                 "incident-000-step_retry", docs)
    with pytest.raises(ValueError, match="requests.json"):
        load_incident_bundle(path)


# ============================================ engine integration (§14)
def _spy_victims(eng):
    """Ground-truth corruption victims: ``last_corrupted_uids`` resets
    every decode attempt, so accumulate it as the run proceeds."""
    victims = []
    orig = eng._faults.corrupt_tokens

    def spy(toks, active, uid_of):
        out = orig(toks, active, uid_of)
        victims.extend(u for u in eng._faults.last_corrupted_uids
                       if u not in victims)
        return out

    eng._faults.corrupt_tokens = spy
    return victims


def _chaos_engine(setup, tmp_path, fault_spec, **ecfg_kw):
    cfg, model, params, prompts = setup
    inc = str(tmp_path / "incidents")
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, max_len=MAX_LEN, prefill_bucket=8,
        fault_spec=fault_spec, incident_dir=inc, **ecfg_kw))
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    return eng, inc


def test_nan_fault_yields_one_bundle_with_victim_uid(setup, tmp_path):
    """nan corruption -> retry -> exactly one step_retry bundle naming
    the seeded victim's uid; the report validates and names the trigger."""
    spec = FaultSpec(seed=5, nan_logits_rate=1.0, max_faults=1)
    eng, inc = _chaos_engine(setup, tmp_path, spec)
    victims = _spy_victims(eng)
    eng.drain()
    assert eng.metrics()["step_retries"] == 1
    bundles = sorted(os.listdir(inc))
    assert len(bundles) == 1 and bundles[0].endswith("step_retry")
    assert eng.incidents == [os.path.join(inc, bundles[0])]
    bundle = load_incident_bundle(eng.incidents[0])
    trig = bundle["trigger.json"]["trigger"]
    assert trig["detector"] == "step_retry"
    # the spied injector victim list is the attribution oracle
    assert victims and trig["uid"] == victims[0]
    assert any(trig["uid"] in r["uids"]
               for r in bundle["flight.json"]["records"])
    assert report_main([eng.incidents[0], "--validate"]) == 0


def test_exception_fault_yields_one_bundle(setup, tmp_path):
    """A whole-step exception is unattributable (no single victim) but
    must still produce exactly one step_retry bundle."""
    spec = FaultSpec(seed=0, step_exception_rate=1.0, max_faults=1)
    eng, inc = _chaos_engine(setup, tmp_path, spec)
    eng.drain()
    bundles = sorted(os.listdir(inc))
    assert len(bundles) == 1 and bundles[0].endswith("step_retry")
    bundle = load_incident_bundle(eng.incidents[0])
    assert bundle["trigger.json"]["trigger"]["uid"] is None
    assert report_main([eng.incidents[0], "--validate"]) == 0


def test_crash_fault_dump_incident_on_supervision(setup, tmp_path):
    """InjectedCrash kills the step loop before the sweep runs, so the
    supervisor dumps from the crashed engine — the serve.py restart
    path — and the bundle's flight window describes the death."""
    spec = FaultSpec(seed=2, crash_rate=1.0, max_faults=1)
    eng, inc = _chaos_engine(setup, tmp_path, spec)
    with pytest.raises(InjectedCrash) as e:
        eng.drain()
    path = eng.dump_incident("injected_crash", reason=str(e.value))
    assert path is not None and os.path.basename(path).endswith(
        "injected_crash")
    bundle = load_incident_bundle(path)
    assert bundle["trigger.json"]["trigger"]["detector"] \
        == "injected_crash"
    assert report_main([path, "--validate"]) == 0


def test_clean_run_yields_zero_bundles(setup, tmp_path):
    """The false-positive gate: an unfaulted run of equal length writes
    nothing — the incident dir is never even created."""
    eng, inc = _chaos_engine(setup, tmp_path, None)
    fin = eng.drain()
    assert len(fin) == 5
    assert eng.incidents == [] and not os.path.exists(inc)
    assert eng.metrics()["anomalies_fired"] == 0
    assert eng.metrics()["flight_recorded"] > 0


def test_bundle_seq_survives_restart(setup, tmp_path):
    """A fresh engine (post-supervisor-restart) must not overwrite the
    previous engine's bundles: the sequence number comes from disk."""
    spec = FaultSpec(seed=5, nan_logits_rate=1.0, max_faults=1)
    eng1, inc = _chaos_engine(setup, tmp_path, spec)
    eng1.drain()
    eng2, _ = _chaos_engine(setup, tmp_path, spec)
    eng2.drain()
    names = sorted(os.listdir(inc))
    assert len(names) == 2
    assert names[0].startswith("incident-000-")
    assert names[1].startswith("incident-001-")


def test_global_cooldown_one_bundle_per_storm(setup, tmp_path):
    """poison_rate=1 faults every attempt of every request; the global
    bundle cooldown must collapse the storm into a single bundle."""
    spec = FaultSpec(seed=0, poison_rate=1.0)
    eng, inc = _chaos_engine(setup, tmp_path, spec, max_retries=1)
    eng.drain()
    assert eng.metrics()["step_retries"] > 1         # storm really raged
    assert eng.metrics()["quarantined"] == 5
    assert len(os.listdir(inc)) == 1


def test_incident_report_timeline_and_hints(setup, tmp_path, capsys):
    """The human-facing output: timeline marks the trigger step, hints
    name the root cause, --journal correlation resolves the uid."""
    journal = str(tmp_path / "j.jsonl")
    spec = FaultSpec(seed=5, nan_logits_rate=1.0, max_faults=1)
    cfg, model, params, prompts = setup
    inc = str(tmp_path / "incidents")
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, max_len=MAX_LEN, prefill_bucket=8, fault_spec=spec,
        incident_dir=inc, journal_path=journal))
    victims = _spy_victims(eng)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    eng.drain()
    [name] = os.listdir(inc)
    rc = report_main([os.path.join(inc, name), "--journal", journal])
    out = capsys.readouterr().out
    assert rc == 0
    assert "trigger step_retry" in out
    assert "timeline" in out and "root-cause hints" in out
    assert "<< step_retry" in out
    # journal correlation: the victim uid's story names its lifecycle
    assert victims and f"uid {victims[0]}" in out
