"""The reduction from trace to device metrics (``bench.trace``): busy
union and idle share, pooling of the kernel events, and the attribution
of idle gaps to host spans."""
from pathlib import Path

import pytest

from bench.trace import Event, label, load, summarize

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def host(name, s, e, line="python"):
    return Event(HOST, line, name, s, e - s)


def dev(name, s, e, kernel=False, plane=DEV0):
    return Event(plane, "XLA Ops", name, s, e - s, kernel)


EVENTS = [
    host("bench.window", 100, 1100),
    host("bench.run", 100, 600),
    host("bench.run", 600, 1100),
    host("DevicePutWithSharding", 100, 300),
    host("np.asarray(jax.Array)", 880, 1060),
    host("Transpose::Execute", 500, 760, line="pjrt-tpu-tasks"),
    host("Delinearize", 880, 1000, line="pjrt-tpu-tasks"),
    dev("custom-call.1", 250, 400, kernel=True),
    dev("fusion.2", 350, 500),           # overlaps the kernel
    dev("custom-call.1", 750, 900, kernel=True),
    dev("fusion.3", 1050, 1200),         # runs past the window's end
    dev("fusion.9", 20, 90),             # before the window
]


def test_busy_union_and_idle_share():
    s = summarize(EVENTS)
    assert s.window_s == 1000e-9
    # [250, 500] + [750, 900] + [1050, 1100]
    assert abs(s.busy_s - 450e-9) < 1e-15
    assert abs(s.idle_share - 0.55) < 1e-12
    assert s.chips == 1


def test_kernel_events_pooled():
    s = summarize(EVENTS)
    assert abs(s.kernel_s - 300e-9) < 1e-15
    ops = dict(s.device_ops)
    assert abs(ops["custom-call.1"] - 300e-9) < 1e-15
    assert abs(ops["fusion.3"] - 50e-9) < 1e-15
    assert "fusion.9" not in ops
    assert s.device_ops[0][0] == "custom-call.1"


def test_gaps_go_to_the_innermost_span_of_the_calling_thread():
    gaps = dict(summarize(EVENTS).idle_gaps)
    # [100, 250] inside the placing of inputs; [900, 1050] inside the
    # fetch of results, though a worker thread has a span open there too;
    # [500, 750] inside nothing of the caller's but the second bench.run:
    # the worker thread's span says what it waits on
    assert abs(gaps["DevicePutWithSharding"] - 150e-9) < 1e-15
    assert abs(gaps["np.asarray(jax.Array)"] - 150e-9) < 1e-15
    assert abs(gaps["bench.run > Transpose::Execute"] - 250e-9) < 1e-15
    assert abs(sum(gaps.values()) - 550e-9) < 1e-15


def test_a_caller_wait_with_no_worker_span_stays_the_callers():
    events = [e for e in EVENTS if e.name != "Transpose::Execute"]
    gaps = dict(summarize(events).idle_gaps)
    assert abs(gaps["bench.run"] - 250e-9) < 1e-15


def test_busy_is_averaged_over_the_chips_used():
    events = EVENTS + [dev("fusion.4", 100, 1100, plane=DEV1)]
    s = summarize(events)
    assert s.chips == 2
    assert abs(s.busy_s - (450e-9 + 1000e-9) / 2) < 1e-15
    assert abs(sum(v for _k, v in s.idle_gaps) - 550e-9 / 2) < 1e-15


def test_device_ops_are_labelled_by_instruction_op_and_shape():
    kernel = ('%program.15 = s32[64,100352]{1,0:T(8,128)} custom-call('
              's8[64,2048]{1,0:T(8,128)(4,1)S(1)} %copy-done.4), '
              'custom_call_target="tpu_custom_call"')
    assert label(kernel) == "program.15 custom-call s32[64,100352]"
    assert label("%copy-start.2 = (s8[3,64,1]{1,0}, u32[]{:S(2)}) "
                 "copy-start(s8[3,64,1]{1,0} %p)") == \
        "copy-start.2 copy-start (tuple)"
    assert label("fusion.3") == "fusion.3"


# -- a recorded trace: 1 s of stablelm_1_6b.decode64_int4 on a TPU v5e ---

RECORDED = Path(__file__).parent / "data" / "decode64.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    events = load(str(RECORDED))
    return events, summarize(events)


def _covered_ns(intervals):
    """Length covered by ``intervals``, by a coverage count over their
    sorted edges (not by merging them)."""
    edges = sorted([(s, 1) for s, _ in intervals]
                   + [(e, -1) for _, e in intervals])
    covered, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_recorded_busy_and_idle(recorded):
    events, s = recorded
    [win] = [e for e in events if e.name == "bench.window"]
    dev = [(max(e.start_ns, win.start_ns), min(e.end_ns, win.end_ns))
           for e in events if e.plane == DEV0]
    dev = [(a, b) for a, b in dev if b > a]
    assert s.chips == 1
    assert s.window_s == win.dur_ns / 1e9
    assert abs(s.busy_s - _covered_ns(dev) / 1e9) < 1e-9
    assert 0.0 < s.idle_share < 1.0


def test_recorded_kernels_pooled(recorded):
    events, s = recorded
    kernels = [e for e in events if e.kernel]
    # one Pallas kernel per matmul step: 8 steps, called back to back
    assert {e.name.split()[1] for e in kernels} == {"custom-call"}
    assert abs(s.kernel_s - sum(e.dur_ns for e in kernels) / 1e9) < 1e-9
    assert 0.0 < s.kernel_s <= s.busy_s
    assert s.device_ops[0][0] == "program.15 custom-call s32[64,100352]"


def test_recorded_gaps_on_host_spans(recorded):
    _events, s = recorded
    gaps = dict(s.idle_gaps)
    idle = s.window_s - s.busy_s
    # the ten largest of the gaps' causes cover nearly all idle time
    assert 0.99 * idle <= sum(gaps.values()) <= idle + 1e-9
    # the caller waits most on the results coming back to the host
    assert s.idle_gaps[0][0] == "np.asarray(jax.Array)"
