"""telemetry/trace.py interval semantics: per-(pid,tid) merging of
overlapping events, zero-duration events, out-of-order completion, and
the crashed-run contract — unmatched B/b begins close at the trace end
with a `truncated` flag instead of raising or silently dropping."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.telemetry.trace import (  # noqa: E402
    _merged_total_us, classify, summarize_events)


def X(name, ts, dur, pid=1, tid=1):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur,
            "pid": pid, "tid": tid}


# -- interval merge core ------------------------------------------------------

def test_merged_total_overlap_containment_and_disjoint():
    # [0,10) + [5,15) overlap -> 15; [20,30) disjoint -> +10;
    # [21,25) contained -> +0
    assert _merged_total_us([(0, 10), (5, 15), (20, 30), (21, 25)]) == 25


def test_merged_total_out_of_order_input():
    # completion order != start order: sort inside the merge handles it
    assert _merged_total_us([(20, 30), (0, 10), (5, 15)]) == 25


def test_merged_total_zero_duration():
    assert _merged_total_us([(5, 5), (5, 5), (7, 7)]) == 0
    assert _merged_total_us([]) == 0


# -- same-(pid,tid) overlapping events ---------------------------------------

def test_overlapping_same_thread_events_merge_not_sum():
    """A wrapper op re-reporting a nested op on the SAME thread must not
    double-count; the same ops on ANOTHER thread must sum."""
    events = [
        X("all-gather-start.1", 0, 100),
        X("all-gather-start.2", 50, 100),          # overlaps on tid 1
        X("all-gather-start.3", 0, 100, tid=2),    # concurrent on tid 2
    ]
    s = summarize_events(events)
    assert s["collective_ms"] == (150 + 100) / 1e3
    assert s["collective_by_op_ms"]["all-gather"] == 0.25
    assert s["events_classified"] == 3


def test_zero_duration_events_counted_but_costless():
    s = summarize_events([X("fusion.1", 10, 0), X("dot.1", 10, 5)])
    assert s["compute_ms"] == 0.005
    assert s["events_classified"] == 2
    assert "truncated" not in s


def test_out_of_order_completion_across_async_pairs():
    """Two async ops on one pid where the second-started finishes first
    (id-keyed matching, not stack order)."""
    events = [
        {"ph": "b", "name": "all-gather.1", "ts": 0, "pid": 1, "id": "a"},
        {"ph": "b", "name": "all-reduce.1", "ts": 10, "pid": 1, "id": "b"},
        {"ph": "e", "name": "all-reduce.1", "ts": 20, "pid": 1, "id": "b"},
        {"ph": "e", "name": "all-gather.1", "ts": 40, "pid": 1, "id": "a"},
    ]
    s = summarize_events(events)
    assert s["collective_by_op_ms"]["all-gather"] == 0.04
    assert s["collective_by_op_ms"]["all-reduce"] == 0.01
    assert "truncated" not in s


# -- truncated traces (crashed run mid-interval) ------------------------------

def test_unmatched_async_start_closes_at_trace_end_with_flag():
    """The op still open when the run died is the one the postmortem
    wants: close it at the trace end, flag the summary as truncated."""
    events = [
        X("dot.1", 0, 100),
        {"ph": "b", "name": "all-gather-start.7", "ts": 20, "pid": 1,
         "id": "g"},
        X("fusion.2", 100, 400),  # extends the trace end to 500
        # no matching 'e': the run crashed mid-collective
    ]
    s = summarize_events(events)
    assert s["truncated"] is True
    assert s["truncated_intervals"] == 1
    # closed at max_ts=500: [20, 500) -> 480 us
    assert s["collective_ms"] == 0.48
    assert s["collective_by_op_ms"]["all-gather"] == 0.48


def test_truncated_async_interval_merges_with_same_thread_ops():
    """The closed-at-end interval must land under the begin event's
    (pid, tid) so it interval-merges with that thread's completed ops —
    keying it under a synthetic thread would double-count the overlap in
    exactly the crashed-run summary truncation exists for."""
    events = [
        X("all-reduce.9", 0, 100, pid=1, tid=5),
        {"ph": "b", "name": "all-gather.2", "ts": 50, "pid": 1, "tid": 5,
         "id": "g"},
        # trace ends at 100; the open all-gather closes at [50, 100)
    ]
    s = summarize_events(events)
    assert s["truncated_intervals"] == 1
    # merged on tid 5: union of [0,100) and [50,100) is 100 us, not 150
    assert s["collective_ms"] == 0.1


def test_async_close_uses_begin_tid():
    """b/e pairs whose end event lost its tid still attribute to the
    begin's thread (the tid rides in the open-async entry)."""
    events = [
        X("all-to-all.1", 0, 40, pid=1, tid=3),
        {"ph": "b", "name": "all-to-all.2", "ts": 10, "pid": 1, "tid": 3,
         "id": "q"},
        {"ph": "e", "name": "all-to-all.2", "ts": 60, "pid": 1, "id": "q"},
    ]
    s = summarize_events(events)
    # same thread: [0,40) U [10,60) = 60 us merged, not 90 summed
    assert s["collective_ms"] == 0.06


def test_unmatched_sync_begin_closes_at_trace_end():
    events = [
        {"ph": "B", "name": "host/dispatch", "ts": 0, "pid": 9, "tid": 9},
        X("dot.3", 100, 100, pid=1, tid=1),
        # host/dispatch never Ends: the host thread was killed mid-step
    ]
    s = summarize_events(events)
    assert s["truncated"] is True
    assert s["host_ms"]["dispatch"] == 0.2  # [0, 200)


def test_matched_b_e_pairs_and_unmatched_end_ignored():
    """B/E pairs attribute like X events; an E whose B predates the
    capture window has no start to attribute and must not raise."""
    events = [
        {"ph": "E", "name": "host/h2d", "ts": 5, "pid": 1, "tid": 1},
        {"ph": "B", "name": "all-reduce.1", "ts": 10, "pid": 1, "tid": 1},
        {"ph": "E", "name": "all-reduce.1", "ts": 30, "pid": 1, "tid": 1},
    ]
    s = summarize_events(events)
    assert s["collective_ms"] == 0.02
    assert "truncated" not in s


def test_unmatched_framework_noise_not_counted_as_truncated():
    """An unmatched begin whose name classifies as framework noise is
    excluded from the totals AND from the truncation count."""
    events = [
        {"ph": "B", "name": "ThunkExecutor::Run", "ts": 0, "pid": 1,
         "tid": 1},
        X("dot.1", 0, 10),
    ]
    s = summarize_events(events)
    assert "truncated" not in s
    assert s["events_classified"] == 1


def test_classify_contract_unchanged():
    assert classify("all-gather-start.12") == "collective"
    assert classify("reduce-scatter.1") == "collective"
    assert classify("transpose_copy_fusion") == "compute"
    assert classify("host/data_wait") == "host/data_wait"
    assert classify("ThunkExecutor::Run") is None
    assert classify("PjitFunction(train_step)") is None


def test_trace_summary_cli_reports_truncation(tmp_path, capsys):
    """tools/trace_summary.py surfaces the truncation loudly instead of
    presenting a crashed trace as a complete one."""
    import gzip
    import json as _json

    from tools.trace_summary import main as ts_main

    trace = {"traceEvents": [
        X("dot.1", 0, 100),
        {"ph": "b", "name": "all-gather.1", "ts": 50, "pid": 1, "id": "x"},
    ]}
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt", encoding="utf-8") as f:
        _json.dump(trace, f)
    out_json = tmp_path / "s.json"
    summary = ts_main(["--trace", str(path), "--json", str(out_json)])
    assert summary["truncated"] is True
    assert "never completed" in capsys.readouterr().out
    assert _json.loads(out_json.read_text())["truncated_intervals"] == 1


# -- the per-KIND collective split (round 15) ---------------------------------

def test_collective_kind_split():
    """collective_kind_ms buckets every collective root into its class —
    all-gather / all-reduce / reduce-scatter / collective-permute /
    all-to-all, everything else under 'other' — with the same per-thread
    interval merge as the totals, so multi-chip breakdowns can say WHICH
    collective class a variant pays for."""
    from bert_pytorch_tpu.telemetry.trace import collective_kind

    assert collective_kind("all-gather") == "all-gather"
    assert collective_kind("reduce-scatter") == "reduce-scatter"
    assert collective_kind("send") == "other"
    events = [
        X("all-gather-start.1", 0, 100),
        X("all-gather-done.1", 100, 20),      # same class, same thread
        X("all-reduce.7", 0, 50),
        X("collective-permute-start.2", 200, 30),
        X("all-to-all.1", 300, 10),
        X("partition-id.1", 400, 5),          # -> other
        X("dot.1", 500, 40),                  # compute: not in the split
    ]
    s = summarize_events(events, steps=1, n_devices=1)
    kinds = s["collective_kind_ms"]
    assert kinds["all-gather"] == 0.12
    assert kinds["all-reduce"] == 0.05
    assert kinds["collective-permute"] == 0.03
    assert kinds["all-to-all"] == 0.01
    assert kinds["other"] == 0.005
    assert "reduce-scatter" not in kinds       # absent kinds are omitted
    assert s["collective_kind_ms_per_step_device"]["all-gather"] == 0.12
    # classes overlapping in time are each fully attributed (the one
    # collective total merges the overlap away — kinds may sum past it);
    # with NO cross-class overlap the split decomposes the total exactly
    disjoint = [X("all-gather.1", 0, 10), X("all-reduce.1", 20, 10)]
    s2 = summarize_events(disjoint)
    assert abs(sum(s2["collective_kind_ms"].values())
               - s2["collective_ms"]) < 1e-9


def test_collective_kind_split_merges_within_class():
    """Two overlapping roots of the SAME class on one thread merge (no
    double-count), while different classes overlap freely — each class
    reports its own merged time."""
    events = [
        X("all-gather-start.1", 0, 100),
        X("all-gather-start.2", 50, 100),     # overlap: class total 150
        X("all-reduce.1", 0, 100),            # different class, same span
    ]
    s = summarize_events(events)
    kinds = s["collective_kind_ms"]
    assert kinds["all-gather"] == 0.15
    assert kinds["all-reduce"] == 0.1
    # cross-class overlap merges away in the one collective total
    assert s["collective_ms"] == 0.15
