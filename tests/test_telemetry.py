"""The fleet telemetry plane (ISSUE 7).

Five claims:

* **off is free** — a learner built WITHOUT telemetry reproduces the
  PR-2 goldens bitwise: the plane's existence changes nothing.
* **on is exact** — an instrumented run's integer counters equal the
  uninstrumented run's, every streamed record is schema-valid, and the
  stream's cumulative totals equal the engine's host counters exactly
  (floats bitwise — both sides accumulate the same float64 running sum).
* **the schema is a contract** — round records survive a JSON
  round-trip; a version-mismatched or mistyped record is REJECTED.
* **counters survive checkpoints** — ``counters_state`` →
  ``save_protocol_state`` → ``load_counters`` → ``restore_counters``
  continues the stream as ONE continuous record (rounds contiguous
  across the resume boundary, cumulatives monotone).
* **the program names its work** — the driver's host spans nest per
  chunk and share its round in a profiler trace, the chunk program
  carries the device scopes, and ``trace_counts`` sees each compile.
"""
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro.checkpoint.io import (
    load_counters, load_protocol_state, save_protocol_state,
)
from repro.config import (
    NetworkConfig, ProtocolConfig, TelemetryConfig, TrainConfig, get_arch,
)
from repro.core.protocol import DecentralizedLearner
from repro.data.pipeline import LearnerStreams
from repro.data.synthetic import GraphicalModelStream
from repro.models.cnn import cnn_loss, init_cnn_params
from repro.telemetry import TelemetrySink, get_logger, jsonl_handler
from repro.telemetry.observatory import frontier, load_run, summarize
from repro.telemetry.record import (
    SCHEMA_VERSION, RoundRecord, validate_record,
)
from repro.telemetry.trace import trace_counts

from golden_pr2_capture import CASES, M, ROUNDS, params_sha256
from hypothesis_compat import given, settings, st

with open(os.path.join(os.path.dirname(__file__),
                       "golden_pr2_engine.json")) as f:
    GOLDEN = json.load(f)
GOLDEN_JAX = GOLDEN.get("_meta", {}).get("jax_version")


def _learner(proto, network, telemetry=None, m=M, seed=0):
    cfg = get_arch("drift_mlp", smoke=True)
    streams = LearnerStreams(GraphicalModelStream(seed=0, drift_prob=0.0),
                             m, batch=10, seed=seed)
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b),
        lambda k: init_cnn_params(cfg, k), m, proto,
        TrainConfig(optimizer="sgd", learning_rate=0.05),
        sample_weights=streams.weights, network=network,
        telemetry=telemetry)
    return dl, streams


# ---------------------------------------------------------------------------
# off is free: telemetry=None reproduces the PR-2 goldens bitwise
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    jax.__version__ != GOLDEN_JAX,
    reason=f"bitwise goldens captured under jax {GOLDEN_JAX}")
@pytest.mark.parametrize("name", ["dynamic_net", "periodic_ideal"])
def test_telemetry_disabled_is_bitwise_noop(name):
    proto, network = CASES[name]
    dl, streams = _learner(proto, network, telemetry=None)
    dl.run_chunk(streams.next_chunk(ROUNDS))
    want = GOLDEN[name]
    assert dl.comm_totals == want["comm_totals"]
    assert repr(dl.cumulative_loss) == want["cumulative_loss"]
    assert params_sha256(dl.params) == want["params_sha256"]
    assert dl.link_xfer_totals.tolist() == want["link_xfer_totals"]
    assert repr(dl.network_time) == want["network_time"]


# ---------------------------------------------------------------------------
# on is exact: counters match, records validate, stream == engine
# ---------------------------------------------------------------------------

def test_telemetry_enabled_stream_is_exact(tmp_path):
    proto, network = CASES["dynamic_net"]
    path = str(tmp_path / "run.jsonl")

    plain, streams = _learner(proto, network, telemetry=None)
    plain.run_chunk(streams.next_chunk(ROUNDS))

    telem = TelemetryConfig(path=path, per_link=True, profile=True)
    dl, streams = _learner(proto, network, telemetry=telem)
    dl.run_chunk(streams.next_chunk(ROUNDS))
    dl.recorder.close()

    # instrumentation must not perturb the protocol: params bitwise and
    # every integer counter identical. The float loss counter accumulates
    # differently BY DESIGN — the instrumented engine sums the per-round
    # float64 stream (so the last record equals the counter bitwise)
    # where the plain engine reads the device's float32 chunk total —
    # so it only agrees to float32 resolution.
    assert params_sha256(dl.params) == params_sha256(plain.params)
    assert dl.comm_totals == plain.comm_totals
    assert dl.link_xfer_totals.tolist() == plain.link_xfer_totals.tolist()
    np.testing.assert_allclose(dl.cumulative_loss, plain.cumulative_loss,
                               rtol=1e-6)

    # every line schema-valid; one meta + ROUNDS rounds + >=1 chunk
    with open(path) as f:
        recs = [validate_record(json.loads(line), i + 1)
                for i, line in enumerate(f)]
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "meta"
    rounds = [r for r in recs if r["kind"] == "round"]
    chunks = [r for r in recs if r["kind"] == "chunk"]
    assert len(rounds) == ROUNDS
    assert [r["round"] for r in rounds] == list(range(1, ROUNDS + 1))
    assert chunks and chunks[-1]["rounds_end"] == ROUNDS

    # the stream's cumulative totals ARE the engine's counters (exact:
    # ints by int64 arithmetic, floats by the shared float64 running sum)
    last = rounds[-1]
    assert last["cum_bytes"] == dl.comm_bytes()
    assert last["cum_syncs"] == dl.comm_totals["syncs"]
    assert last["cum_loss"] == dl.cumulative_loss
    assert last["cum_net_time"] == dl.network_time
    assert sum(r["messages"] for r in rounds) == dl.comm_totals["messages"]
    assert sum(r["cohort"] for r in rounds) == dl.comm_totals["model_up"]
    assert chunks[-1]["link_bytes_cum"] == [
        int(x) for x in dl.link_bytes_totals]
    # per-link rows sum to the ledger
    per_link = np.array([r["link_bytes"] for r in rounds], np.int64)
    assert per_link.sum(axis=0).tolist() == chunks[-1]["link_bytes_cum"]

    # the observatory reproduces the frontier from the file ALONE
    run = load_run(path)
    fr = frontier(run)
    assert fr[-1] == [ROUNDS, dl.comm_bytes(), dl.cumulative_loss]
    card = summarize(run)
    assert card["cum_bytes"] == dl.comm_bytes()
    assert card["cum_syncs"] == dl.comm_totals["syncs"]
    assert card["rounds"] == ROUNDS
    assert card["link_class_bytes"] is not None
    assert card["profile"] is not None        # profile=True


def test_telemetry_no_extra_device_fetches(tmp_path, monkeypatch):
    """The instrumented chunk path performs exactly ONE ``device_get`` —
    the same single fetch the uninstrumented fold already pays."""
    proto, network = CASES["dynamic_ideal"]
    telem = TelemetryConfig(path=str(tmp_path / "run.jsonl"))
    dl, streams = _learner(proto, network, telemetry=telem)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    dl.run_chunk(streams.next_chunk(8))
    dl.recorder.close()
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# names in the program: host spans, device scopes, trace/compile counts
# ---------------------------------------------------------------------------

# (span, its parent) on the driver path, per chunk
SPAN_PARENTS = {
    "repro.loop.chunk": None,
    "repro.streams.next_chunk": "repro.loop.chunk",
    "repro.engine.run_chunk": "repro.loop.chunk",
    "repro.engine.dispatch": "repro.engine.run_chunk",
    "repro.engine.fetch": "repro.engine.run_chunk",
    "repro.engine.accumulate": "repro.engine.run_chunk",
    "repro.loop.trajectory": "repro.loop.chunk",
}


def test_driver_spans_nest_and_share_round(tmp_path):
    """Two chunks of a tiny flat fleet under ``jax.profiler`` on the CPU,
    reduced by the benchmark's own trace reader: every span of the driver
    path appears once per chunk, inside its parent, and the spans of one
    chunk carry that chunk's first round."""
    from repro.train.loop import run_protocol_training
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from perfbench import trace as ptrace

    cfg = get_arch("drift_mlp", smoke=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(ptrace.WINDOW_SPAN):
            run_protocol_training(
                lambda p, b: cnn_loss(cfg, p, b),
                lambda k: init_cnn_params(cfg, k),
                GraphicalModelStream(seed=0, drift_prob=0.0), m=4, rounds=6,
                protocol=ProtocolConfig(kind="dynamic", b=1, delta=0.5,
                                        layout="flat"),
                train=TrainConfig(optimizer="sgd", learning_rate=0.05),
                chunk_size=3)
    finally:
        jax.profiler.stop_trace()
    host = [h for h in ptrace.reduce_dir(str(tmp_path), chips=1).host
            if h.name in SPAN_PARENTS]
    by_round = {}
    for h in host:
        by_round.setdefault(h.stats["round"], []).append(h)
    assert sorted(by_round) == [0, 3]
    for spans in by_round.values():
        named = {h.name: h for h in spans}
        assert sorted(h.name for h in spans) == sorted(SPAN_PARENTS)
        for name, parent in SPAN_PARENTS.items():
            if parent is not None:
                inner, outer = named[name], named[parent]
                assert outer.start <= inner.start <= inner.end <= outer.end


def _lowered_scopes(layout):
    """The ``repro.*`` components of the name paths in the lowered
    chunk program of a dynamic fleet."""
    cfg = get_arch("drift_mlp", smoke=True)
    streams = LearnerStreams(GraphicalModelStream(seed=0, drift_prob=0.0),
                             4, batch=10, seed=0)
    dl = DecentralizedLearner(
        lambda p, b: cnn_loss(cfg, p, b), lambda k: init_cnn_params(cfg, k),
        4, ProtocolConfig(kind="dynamic", b=1, delta=0.5, layout=layout),
        TrainConfig(optimizer="sgd", learning_rate=0.05))
    text = dl._chunk.lower(dl.params, dl.opt_state, dl.sync_state,
                           streams.next_chunk(2)).as_text(debug_info=True)
    return {c for path in re.findall(r'loc\("([^"]*)"', text)
            for c in path.split("/") if c.startswith("repro.")}


@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_chunk_program_carries_named_scopes(layout):
    stages = {"repro.local_update", "repro.sync", "repro.sync.condition",
              "repro.sync.cohort", "repro.sync.aggregate",
              "repro.sync.commit"}
    # the blocked fleet plane is the stacked leaves themselves: nothing is
    # raveled or unraveled, so the flat layout names the same scopes
    assert _lowered_scopes(layout) == stages


def test_trace_counts_see_each_compile_of_the_chunk():
    """A second chunk of the same length is served from jit's cache and
    counts nothing; a new length traces and compiles the chunk once."""
    proto, network = CASES["dynamic_ideal"]
    dl, streams = _learner(proto, network)

    def chunk_counts():
        return trace_counts().get("chunk", {"traces": 0, "compiles": 0})

    dl.run_chunk(streams.next_chunk(2))
    before = chunk_counts()
    dl.run_chunk(streams.next_chunk(2))
    assert chunk_counts() == before
    dl.run_chunk(streams.next_chunk(3))
    after = chunk_counts()
    assert after == {"traces": before["traces"] + 1,
                     "compiles": before["compiles"] + 1}


# ---------------------------------------------------------------------------
# the schema is a contract
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(rnd=st.integers(1, 10**9), msgs=st.integers(0, 10**6),
       cohort=st.integers(0, 512), sync=st.integers(0, 1),
       loss=st.floats(allow_nan=False, allow_infinity=False, width=64),
       nt=st.floats(min_value=0, max_value=1e12),
       link=st.one_of(st.none(), st.lists(
           st.integers(0, 2**50), min_size=1, max_size=8)))
def test_round_record_json_roundtrip(rnd, msgs, cohort, sync, loss, nt,
                                     link):
    rec = RoundRecord(
        round=rnd, loss=loss, cum_loss=loss, divergence=0.0,
        messages=msgs, cohort=cohort, sync=sync, full_sync=0,
        cum_syncs=sync, num_active=cohort, net_time=nt, cum_net_time=nt,
        round_bytes=cohort * 64, cum_bytes=cohort * 64,
        link_bytes=tuple(link) if link else None)
    back = RoundRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert back == rec


def test_round_record_async_fields_roundtrip_and_card(tmp_path):
    """The optional in-flight/age fields survive the JSON round-trip,
    and a state-carrying run's card (``summarize``) histograms the
    chunk-end trigger-state snapshot."""
    rec = RoundRecord(
        round=1, loss=1.0, cum_loss=1.0, divergence=0.0, messages=0,
        cohort=0, sync=0, full_sync=0, cum_syncs=0, num_active=4,
        net_time=0.0, cum_net_time=0.0, round_bytes=0, cum_bytes=0,
        inflight=3, max_age=7)
    back = RoundRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert back == rec
    with pytest.raises(ValueError, match="must be an integer"):
        RoundRecord.from_dict({**rec.to_dict(), "inflight": 1.5})

    from repro.core.sync import PROTOCOLS
    path = str(tmp_path / "stale.jsonl")
    dl, streams = _learner(PROTOCOLS["stale"].with_params(tau=3), None,
                           telemetry=TelemetryConfig(path=path))
    dl.run_chunk(streams.next_chunk(12))
    dl.recorder.close()
    card = summarize(load_run(path))
    ages = card["state_ages"]["staleness"]
    assert ages["min"] >= 0 and ages["max"] <= 3
    assert sum(ages["hist"].values()) == M       # one bucket per learner
    assert all(r["max_age"] is not None for r in load_run(path).rounds)


def test_round_record_rejects_bad_streams():
    base = RoundRecord(
        round=1, loss=1.0, cum_loss=1.0, divergence=0.0, messages=0,
        cohort=0, sync=0, full_sync=0, cum_syncs=0, num_active=4,
        net_time=0.0, cum_net_time=0.0, round_bytes=0,
        cum_bytes=0).to_dict()
    with pytest.raises(ValueError, match="version mismatch"):
        RoundRecord.from_dict({**base, "v": SCHEMA_VERSION + 1})
    with pytest.raises(ValueError, match="not a round record"):
        RoundRecord.from_dict({**base, "kind": "meta"})
    with pytest.raises(ValueError, match="missing fields"):
        RoundRecord.from_dict(
            {k: v for k, v in base.items() if k != "cum_bytes"})
    with pytest.raises(ValueError, match="must be an integer"):
        RoundRecord.from_dict({**base, "cum_bytes": 1.5})
    with pytest.raises(ValueError, match="unknown fields"):
        RoundRecord.from_dict({**base, "surprise": 1})
    with pytest.raises(ValueError, match="unknown record kind"):
        validate_record({"kind": "mystery", "v": SCHEMA_VERSION})


# ---------------------------------------------------------------------------
# counters survive checkpoints: one continuous stream across a resume
# ---------------------------------------------------------------------------

def test_counter_continuity_across_checkpoint_resume(tmp_path):
    proto, network = CASES["dynamic_net"]
    path = str(tmp_path / "run.jsonl")
    ckpt = str(tmp_path / "ckpt")

    telem = TelemetryConfig(path=path, per_link=True)
    dl, streams = _learner(proto, network, telemetry=telem)
    dl.run_chunk(streams.next_chunk(ROUNDS))
    dl.recorder.close()
    save_protocol_state(ckpt, dl.params, dl.opt_state, dl.sync_state,
                        protocol=proto, counters=dl.counters_state())
    mid = {"bytes": dl.comm_bytes(), "loss": dl.cumulative_loss,
           "syncs": dl.comm_totals["syncs"]}

    # resume: fresh process, restore state + counters, append the stream
    dl2, streams2 = _learner(
        proto, network,
        telemetry=TelemetryConfig(path=path, per_link=True, append=True))
    # params + sync state round-trip (the opt npz loses the OptState
    # container — its round trip is test_spec's subject, not ours)
    params, _, sync = load_protocol_state(ckpt)
    dl2.params, dl2.sync_state = params, sync
    saved = load_counters(ckpt)
    assert saved is not None and saved["rounds"] == ROUNDS
    dl2.restore_counters(saved)
    assert dl2.comm_totals == dl.comm_totals
    assert dl2.cumulative_loss == dl.cumulative_loss
    streams2.next_chunk(ROUNDS)                 # replay the consumed data
    dl2.run_chunk(streams2.next_chunk(ROUNDS))
    dl2.recorder.close()

    run = load_run(path)
    assert run.resumed                           # a second meta was written
    assert run.metas[-1]["resumed_rounds"] == ROUNDS
    got = [r["round"] for r in run.rounds]
    assert got == list(range(1, 2 * ROUNDS + 1))   # contiguous across resume
    last = run.rounds[-1]
    assert last["cum_bytes"] == dl2.comm_bytes() > mid["bytes"]
    assert last["cum_syncs"] == dl2.comm_totals["syncs"] >= mid["syncs"]
    assert last["cum_loss"] == dl2.cumulative_loss > mid["loss"]


def test_restore_counters_rejects_wrong_shape():
    proto, network = CASES["dynamic_ideal"]
    dl, _ = _learner(proto, network)
    good = dl.counters_state()
    with pytest.raises(ValueError):
        dl.restore_counters(
            {**good, "cumulative_loss_per_learner": [0.0] * (M + 1)})
    with pytest.raises(ValueError):
        dl.restore_counters(
            {**good, "comm_totals": {**good["comm_totals"], "bogus": 1}})


# ---------------------------------------------------------------------------
# the event logger and the lint rule that keeps library code on it
# ---------------------------------------------------------------------------

def test_event_logger_routes_to_jsonl(tmp_path):
    log = get_logger()
    assert not log.enabled                       # silent by default
    log.event("ignored", x=1)                    # no handlers: no-op
    with TelemetrySink(str(tmp_path / "ev.jsonl")) as sink:
        handler = log.add_handler(jsonl_handler(sink))
        try:
            log.event("train_step", step=3, loss=0.5)
        finally:
            log.remove_handler(handler)
    with open(tmp_path / "ev.jsonl") as f:
        rec = validate_record(json.loads(f.read()))
    assert rec["kind"] == "event" and rec["event"] == "train_step"
    assert rec["step"] == 3
    assert not log.enabled


def test_lint_print_outside_cli():
    from repro.analysis.lint import lint_source
    lib = "def f():\n    print('x')\n"
    assert [f.rule for f in lint_source(lib, "repro/core/foo.py")] == [
        "print-outside-cli"]
    # __main__.py IS the CLI
    assert lint_source(lib, "repro/telemetry/__main__.py") == []
    # launch modules: prints allowed only inside top-level main()
    entry = "def main():\n    print('ok')\n"
    assert lint_source(entry, "repro/launch/train.py") == []
    assert [f.rule for f in lint_source(lib, "repro/launch/train.py")] == [
        "print-outside-cli"]


# ---------------------------------------------------------------------------
# observatory CLI smoke (direct main() calls — no subprocess)
# ---------------------------------------------------------------------------

def test_observatory_cli_smoke(tmp_path, capsys):
    from repro.telemetry.__main__ import main
    path = str(tmp_path / "cli.jsonl")
    assert main(["record", "--out", path, "--rounds", "20", "--m", "4",
                 "--chunk", "16", "--per-link", "--profile"]) == 0
    capsys.readouterr()                          # drain the record banner
    assert main(["summarize", path]) == 0
    card = json.loads(capsys.readouterr().out)
    assert card["rounds"] == 20 and card["m"] == 4
    assert main(["frontier", path]) == 0
    fr = json.loads(capsys.readouterr().out)
    assert len(fr) >= 1 and fr[-1][0] == 20
    assert main(["tail", path, "-n", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3
    assert main(["prom", path]) == 0
    prom = capsys.readouterr().out
    assert "repro_comm_bytes_total" in prom
    assert "repro_rounds_total 20" in prom
    assert main(["costs", path]) == 0
    costs = json.loads(capsys.readouterr().out)
    assert costs["rounds"] == 20 and costs["est_total_flops"] > 0
