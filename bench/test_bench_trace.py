"""The trace reduction on a small synthetic trace, and the peak table."""
import pytest

from bench import trace_reduce as tr

# device ops on one chip (seconds): two overlap, one is the kernel
EVENTS = [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 2.5),
          ("clustered_agg.3", 4.0, 4.5), ("fusion.1", 6.0, 6.5)]
SPANS = [("epoch", 0.5, 3.0), ("federate", 3.0, 4.2), ("epoch", 5.0, 6.4)]


def test_busy_union_merges_overlaps():
    assert tr.busy_union([(s, e) for _, s, e in EVENTS]) == \
        [(1.0, 2.5), (4.0, 4.5), (6.0, 6.5)]


def test_busy_seconds_clips_to_window():
    iv = [(s, e) for _, s, e in EVENTS]
    assert tr.busy_seconds(iv, 0.0, 8.0) == pytest.approx(2.5)
    assert tr.busy_seconds(iv, 2.0, 6.25) == pytest.approx(0.5 + 0.5 + 0.25)


def test_kernel_seconds_sums_by_name():
    assert tr.kernel_seconds(EVENTS, "clustered_agg") == pytest.approx(0.5)
    assert tr.kernel_seconds(EVENTS, "fusion") == pytest.approx(2.5)
    assert tr.kernel_seconds(EVENTS, "absent") == 0.0


def test_op_totals_by_self_time():
    nested = [("%while.1 = (f32[2]) while(...)", 0.0, 10.0),
              ("%fusion.3 = f32[2] fusion(...)", 1.0, 2.0),
              ("%fusion.4 = f32[2] fusion(...)", 3.0, 5.0),
              ("%fusion.3 = f32[2] fusion(...)", 6.0, 6.5),
              ("%copy.9 = f32[2] copy(...)", 11.0, 11.25)]
    assert tr.op_totals(nested, top=3) == [("while.1", 6.5), ("fusion.4", 2.0),
                                           ("fusion.3", 1.5)]


def test_gaps_attributed_to_host_spans():
    busy = tr.busy_union([(s, e) for _, s, e in EVENTS])
    gaps = tr.idle_gaps(busy, SPANS, 0.0, 8.0)
    assert gaps == [("epoch", 0.0, 1.0), ("federate", 2.5, 4.0),
                    ("epoch", 4.5, 6.0), ("between", 6.5, 8.0)]
    top = tr.gap_totals(gaps, top=2)
    assert [n for n, _ in top] == ["federate", "epoch"]
    assert [d for _, d in top] == pytest.approx([1.5, 1.5])


def test_trace_window_and_events():
    t = tr.Trace({"/device:TPU:0": EVENTS[:2], "/device:TPU:1": EVENTS[2:]},
                 [("bench.window", 0.5, 7.0), ("bench.epoch", 1.0, 2.0)])
    assert t.window("bench.window") == (0.5, 7.0)
    assert t.window("bench.none") is None
    assert len(t.events()) == 4


def test_peaks_by_device_kind():
    p = tr.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        tr.peaks("TPU v9 imaginary")
