"""The readers of the selection and pricing phases, on hand-built span
lists whose self-times are worked out by hand."""
from __future__ import annotations

import pytest

from bench import measure, run

PHASE_METRICS = ("partition_ms_per_point", "place_host_ms_per_point",
                 "place_wait_ms_per_point", "reshape_ms_per_point",
                 "price_baseline_ms_per_point", "price_cim_ms_per_point")
READERS = run.metric_readers(PHASE_METRICS)


def _span(sid, name, ts, dur, parent=None):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "ts_ns": ts, "dur_ns": dur, "attrs": {}}


def _read(name, spans, points=2):
    m = measure.Measurements(spans, points, None, {}, {}, {})
    return READERS[name](m)


def _selection():
    # one selection of 0..11 ms: the IDG lookup 0..1, a partition 1..3, a
    # placement 3..8 (pack 3..4, device 4..7, unpack 7..7.5) and a
    # reshape 8..9.5
    ms = 1_000_000
    return [_span("s", "cache.select", 0, 11 * ms),
            _span("idg", "cache.idg", 0, 1 * ms, "s"),
            _span("pt", "select.partition", 1 * ms, 2 * ms, "s"),
            _span("p", "accel.place", 3 * ms, 5 * ms, "s"),
            _span("pk", "accel.place.pack", 3 * ms, 1 * ms, "p"),
            _span("dv", "accel.place.device", 4 * ms, 3 * ms, "p"),
            _span("up", "accel.place.unpack", 7 * ms, ms // 2, "p"),
            _span("rs", "select.reshape", 8 * ms, 3 * ms // 2, "s")]


def _pricing():
    # two points priced on two threads, 0..4 and 2..6 ms, each with its
    # three phases; the pricing spans' own time is 0.5 ms each
    ms = 1_000_000
    out = []
    for i, t0 in enumerate((0, 2 * ms)):
        out += [_span(f"b{i}", "backend.price", t0, 4 * ms),
                _span(f"pb{i}", "price.baseline", t0, ms, f"b{i}"),
                _span(f"pc{i}", "price.cim", t0 + ms, 2 * ms, f"b{i}"),
                _span(f"pm{i}", "price.macr", t0 + 3 * ms, ms // 2,
                      f"b{i}")]
    return out


@pytest.mark.parametrize("name, want", [
    ("partition_ms_per_point", 2 / 2),
    ("place_host_ms_per_point", (1 + 0.5) / 2),
    ("place_wait_ms_per_point", 3 / 2),
    ("reshape_ms_per_point", 1.5 / 2),
    ("price_baseline_ms_per_point", 2 * 1 / 2),
    ("price_cim_ms_per_point", 2 * (2 + 0.5) / 2),
])
def test_phase_reader_reads_self_time_per_point(name, want):
    assert _read(name, _selection() + _pricing()) == pytest.approx(want)


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_reader_reads_nothing_without_its_spans(name):
    # the parent's program opens only the layer spans
    ms = 1_000_000
    spans = [_span("s", "cache.select", 0, 10 * ms),
             _span("p", "accel.place", 3 * ms, 5 * ms, "s"),
             _span("b", "backend.price", 10 * ms, 4 * ms)]
    assert _read(name, spans) is None
    assert _read(name, _selection() + _pricing(), points=0) is None


def test_layer_metrics_are_the_sum_of_their_phases_and_remainder():
    """What the layer metrics read without the phase spans (as on a
    program that has none) is what they read with them, plus the
    phases."""
    spans = _selection() + _pricing()
    layers = run.metric_readers(("select_ms_per_point",
                                 "price_ms_per_point"))

    def reads(recs):
        m = measure.Measurements(recs, 2, None, {}, {}, {})
        return ({n: r(m) for n, r in layers.items()},
                {n: r(m) for n, r in READERS.items()})

    (old, none), (new, phases) = reads(
        [s for s in spans if not _is_phase(s["name"])]), reads(spans)
    assert set(none.values()) == {None}
    # cache.select keeps 11 - 1 - 2 - 5 - 1.5 ms, accel.place 0.5 ms
    assert new["select_ms_per_point"] == pytest.approx((1.5 + 0.5) / 2)
    assert new["price_ms_per_point"] == pytest.approx(2 * 0.5 / 2)
    assert old["select_ms_per_point"] == pytest.approx((5 + 5) / 2)
    assert new["select_ms_per_point"] + sum(
        phases[n] for n in PHASE_METRICS[:4]) == \
        pytest.approx(old["select_ms_per_point"])
    assert new["price_ms_per_point"] + sum(
        phases[n] for n in PHASE_METRICS[4:]) == \
        pytest.approx(old["price_ms_per_point"])


def _is_phase(name):
    return name.startswith(("select.", "accel.place.", "price."))
