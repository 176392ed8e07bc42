"""The reduction from an ``xplane.pb`` to busy time, idle share and named
gaps, on a hand-written trace whose numbers are known, and on a small trace
recorded on the v5e (``data/v5e_q6_4m.xplane.pb``)."""
import os

import pytest

from benchmark import trace_digest as td

US = 1_000_000          # picoseconds in a microsecond


def _event(meta_id, offset_us, dur_us):
    return (f"events {{ metadata_id: {meta_id} offset_ps: {offset_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _plane(pid, name, lines, metadata):
    meta = "".join(
        f"event_metadata {{ key: {k} value {{ id: {k} name: \"{v}\" }} }} "
        for k, v in metadata.items())
    body = "".join(
        f"lines {{ id: {i} name: \"{ln}\" timestamp_ns: 0 "
        f"{' '.join(evs)} }} " for i, (ln, evs) in enumerate(lines, 1))
    return f"planes {{ id: {pid} name: \"{name}\" {body} {meta} }}"


def synthetic():
    # window 0..1000 us.  ops: [100,300) and nested [150,200); [600,700).
    # a step line covers everything and must not count as busy.
    device = _plane(1, "/device:TPU:0", [
        ("Steps", [_event(9, 0, 1000)]),
        ("XLA Ops", [_event(1, 100, 200), _event(2, 150, 50),
                     _event(1, 600, 100)]),
    ], {1: "fusion.1", 2: "sort.2", 9: "step"})
    # scan.wait covers the gap [300,600) fully; nothing covers [700,1000)
    host = _plane(2, "/host:CPU", [
        ("python3", [_event(1, 0, 1000), _event(2, 290, 320)]),
    ], {1: "bench.window", 2: "scan.wait"})
    import jax.profiler
    return jax.profiler.ProfileData.from_text_proto(device + " " + host)


def test_busy_is_the_union_and_the_window_is_the_annotation():
    planes = td.read_planes(synthetic(), "bench.window", {"scan.wait"}, "tpu")
    d = td.digest_planes(planes)
    assert d["window_s"] == pytest.approx(1000e-6)
    assert d["busy_s"] == pytest.approx(300e-6)      # 200 + 100, nested once
    assert d["devices"] == 1
    ops = dict(d["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-6)
    assert ops["sort.2"] == pytest.approx(50e-6)


def test_idle_before_the_first_and_after_the_last_operation_counts():
    d = td.digest_planes(td.read_planes(synthetic(), "bench.window",
                                        {"scan.wait"}, "tpu"))
    idle = dict(d["idle_gaps"][:2])
    assert idle["sum:scan.wait"] == pytest.approx(300e-6)
    assert idle["sum:host_other"] == pytest.approx(400e-6)   # 100 + 300
    longest = d["idle_gaps"][2:]
    assert [n for n, _ in longest] == ["scan.wait", "host_other",
                                       "host_other"]
    assert longest[0][1] == pytest.approx(300e-6)


def test_no_window_or_no_device_operation_raises():
    planes = td.read_planes(synthetic(), "no.such.window", set(), "tpu")
    with pytest.raises(RuntimeError, match="window annotation"):
        td.digest_planes(planes)
    planes = td.read_planes(synthetic(), "bench.window", set(), "tpu")
    planes["devices"] = {}
    with pytest.raises(RuntimeError, match="no operation ran"):
        td.digest_planes(planes)


def test_host_operations_stand_in_for_the_device_on_the_cpu_alone():
    host = _plane(2, "/host:CPU", [
        ("python3", [_event(1, 0, 1000)]),
        ("tf_XLACpuClient/1", [_event(2, 100, 200)]),
    ], {1: "bench.window", 2: "fusion.3"})
    host = host.replace("metadata_id: 2 ", "metadata_id: 2 stats { "
                        "metadata_id: 7 str_value: 'fusion.3' } ", 1)
    host = host.replace("event_metadata", "stat_metadata { key: 7 value { "
                        "id: 7 name: 'hlo_op' } } event_metadata", 1)
    import jax.profiler
    prof = jax.profiler.ProfileData.from_text_proto(host)
    planes = td.read_planes(prof, "bench.window", set(), "cpu")
    assert td.digest_planes(planes)["busy_s"] == pytest.approx(200e-6)
    with pytest.raises(RuntimeError, match="no /device: plane"):
        td.read_planes(prof, "bench.window", set(), "tpu")


def test_span_share_is_a_union():
    spans = [("scan.wait", 0.0, 6.0), ("scan.wait", 4.0, 8.0),
             ("scan.upload", 1.0, 2.0)]
    assert td.span_share_pct(spans, "scan.wait", 0.0, 10.0) == \
        pytest.approx(80.0)
    assert td.span_share_pct(spans, "scan.wait", 5.0, 10.0) == \
        pytest.approx(60.0)
    assert td.span_share_pct(spans, "scan.decode", 0.0, 10.0) is None


def test_recorded_v5e_trace():
    """Two q6 queries over 4,194,304 rows on one v5e (my chip call 3, PR 26):
    eight launches of the fused program, about 101 ms each, and the scan
    wait before each query's first batch."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_q6_4m.xplane.pb")
    import jax.profiler
    prof = jax.profiler.ProfileData.from_file(path)
    d = td.digest_planes(td.read_planes(
        prof, "bench.window", {"scan.wait", "scan.upload"}, "tpu"))
    assert d["devices"] == 1 and "/device:TPU:0" in d["lines_seen"]
    assert d["busy_s"] == pytest.approx(0.8529529, rel=1e-6)
    assert d["window_s"] == pytest.approx(1.2070302, rel=1e-6)
    idle = {k: v for k, v in d["idle_gaps"] if k.startswith("sum:")}
    assert sum(idle.values()) == pytest.approx(
        d["window_s"] - d["busy_s"], rel=1e-6)
    assert idle["sum:scan.wait"] == pytest.approx(0.2157793, rel=1e-6)
    assert all(len(name) <= td.OP_NAME_CHARS for name, _ in d["device_ops"])
