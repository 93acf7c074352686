"""The trace-to-metric reductions, on a small trace recorded on a TPU v5e
(``data/tpu_trace_small.json``) and on hand-made intervals."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "tpu_trace_small.json"


def _hand_made():
    # device 0 busy [10, 30) u [25, 40) u [60, 70); window [0, 100)
    return trace.Trace(
        ops=[[("a", 10, 30), ("b", 25, 40), ("c", 60, 70), ("d", 120, 130)]],
        programs=[[("jit_truncated_prim", 10, 40), ("jit_other", 60, 70),
                   ("jit_truncated_prim", 95, 105)]],
        host=[("solve", 0, 100), ("shuffle:SortGraph", 40, 58),
              ("dht:lookup", 41, 45)],
        window=(0.0, 100.0))


def test_busy_is_the_union_of_operations_inside_the_window():
    t = _hand_made()
    assert trace.merged(t.ops[0], t.window) == [(10, 40), (60, 70)]
    assert trace.busy_ns(t) == 40
    assert trace.idle_share(t) == pytest.approx(0.6)


def test_busy_is_averaged_over_devices():
    t = _hand_made()
    t.ops.append([("x", 0, 100)])
    assert trace.busy_ns(t) == pytest.approx((40 + 100) / 2)


def test_program_time_is_clipped_to_the_window():
    t = _hand_made()
    assert trace.program_ns(t, [r"^jit_truncated_prim"]) == 30 + 5
    assert trace.program_ns(t, [r"^jit_nothing"]) is None


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    t = _hand_made()
    assert trace.gaps(t) == [(0, 10), (40, 60), (70, 100)]
    assert trace.host_label(t, 50) == "shuffle:SortGraph"
    assert trace.host_label(t, 42) == "dht:lookup"
    assert trace.host_label(t, 150) == trace.NO_SPAN
    assert trace.idle_by_host_span(t) == {"solve": 10 + 30,
                                          "shuffle:SortGraph": 20}


@pytest.fixture(scope="module")
def chip_trace():
    return trace.Trace.from_json(json.loads(DATA.read_text()))


def test_chip_trace_reductions(chip_trace):
    t = chip_trace
    busy = trace.busy_ns(t)
    assert 0 < busy < t.window_ns
    # busy by operations never exceeds the programs that hold them
    prog_busy = sum(e - s for s, e in trace.merged(t.programs[0], t.window))
    assert busy <= prog_busy * 1.001
    gaps = trace.gaps(t)
    assert sum(e - s for s, e in gaps) == pytest.approx(t.window_ns - busy)
    idle = trace.idle_by_host_span(t)
    assert sum(idle.values()) == pytest.approx(t.window_ns - busy)
    assert set(idle) - {trace.NO_SPAN} <= {n for n, _, _ in t.host}


def test_chip_trace_breakdown_names_programs_and_spans(chip_trace):
    b = trace.breakdown(chip_trace)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])
    names = [n for n, _ in b["device_ops"]]
    assert any(n.startswith("jit_") for n in names)


def test_trace_json_round_trip(chip_trace):
    again = trace.Trace.from_json(json.loads(json.dumps(chip_trace.to_json())))
    assert again.window == chip_trace.window
    assert trace.busy_ns(again) == trace.busy_ns(chip_trace)


def test_load_without_a_tpu_plane_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    assert trace.load(str(tmp_path), ["solve"]) is None


def test_program_time_per_request():
    t = _hand_made()
    assert trace.program_ms_per(t, [r"^jit_truncated_prim"], 5) == \
        pytest.approx(35 / 1e6 / 5)
    assert trace.program_ms_per(t, [r"^jit_truncated_prim"], 0) is None
    assert trace.program_ms_per(None, [r"^jit_truncated_prim"], 5) is None
