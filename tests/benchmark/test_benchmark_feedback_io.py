"""The reader of the program's `feedback_flush` span, over hand-made runs:
nothing where the program emits no such span (the parent commit), 0.0 where
it does and none ended inside the window, the quotient where one did."""

import pytest

import doc_rules
from benchmarks import lib


def flush(end_s, dur_ms, where="close"):
    """One `feedback_flush`: `ts` its end in epoch ms, `t0_ns` its start."""
    return {"kind": "feedback_flush", "app": "a", "ts": int(end_s * 1e3),
            "t0_ns": int((end_s * 1e3 - dur_ms) * 1e6), "dur_ms": dur_ms,
            "keys": 55, "bytes": 18428, "where": where}


def run_with(events):
    return {
        "marks": {"first_pass_start": 1000e3, "first_pass_end": 1060e3,
                  "window_open": 1100e3, "window_close": 1145e3},
        "statements": [{"name": "query3", "status": "Completed"}] * 4,
        "events": [{"kind": "result_span", "app": "a", "ts": 1101320,
                    "t0_ns": 1101 * 10**9, "dur_ms": 320.0, "exec_id": 7},
                   *events],
    }


@pytest.mark.parametrize("events, want", [
    ([], None),                                      # the parent's program
    ([flush(1059.9, 79.05)], 0.0),                   # the first pass's close
    ([flush(1059.9, 79.05), flush(1146.0, 30.0, "atexit")], 0.0),
    ([flush(1059.9, 79.05), flush(1101.4, 48.0, "flush"),
      flush(1120.0, 52.0, "flush")], 25.0),          # (48 + 52) / 4
], ids=["no_span", "outside", "before_and_after", "inside"])
def test_feedback_io_reader(events, want):
    reader = lib.Spec(lib.REPO).reader("per_layer", "feedback_io_ms.stmt")
    assert reader.read(run_with(events)) == want


def test_feedback_io_reader_needs_a_window():
    reader = lib.Spec(lib.REPO).reader("per_layer", "feedback_io_ms.stmt")
    run = run_with([flush(1059.9, 79.05)])
    assert reader.read({**run, "statements": []}) is None


def test_feedback_io_reader_declares_what_an_entry_will_carry():
    """The four values its `per_layer` entry (appended in PR 37) is held
    equal to; its list begins with the two `replay6` cells, and a later
    cell that wants its feedback flushes read appends its name."""
    reader = lib.Spec(lib.REPO).reader("per_layer", "feedback_io_ms.stmt")
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "executor + fused pipelines", "ms", "stmt_p50_ms", "program_span")
    entry, = [m for m in lib.Spec(lib.REPO).doc["per_layer"]
              if m["name"] == "feedback_io_ms.stmt"]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"],
            entry["better"]) == (reader.LAYER, reader.UNIT, reader.MOVES,
                                 reader.SOURCE, "lower")
    assert doc_rules.workloads_fault(
        lib.Spec(lib.REPO), "feedback_io_ms.stmt") is None
