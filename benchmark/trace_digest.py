"""From the profiler's ``*.xplane.pb`` to device busy time, idle gaps and
the operations that took most time.  Read with ``jax.profiler.ProfileData``
(nothing but JAX); the capped ``trace.json.gz`` is not used.

The window is the benchmark's own ``TraceAnnotation`` on the host plane, not
first device operation to last: idle before the first and after the last
operation of the window counts as idle.  Busy is the union of the intervals
in which an operation ran on a device (the ``XLA Ops`` line of each
``/device:`` plane), clipped to the window and averaged over the devices.
On the CPU backend (rehearsals only) there is no device plane, and the
host-plane events that carry an ``hlo_op`` stat stand in; on any other
platform a trace without a device plane is an error.

Arithmetic copied from ``tools/profile_digest.py`` (union of intervals); its
two sources (window from the device events, the capped JSON) are not.
"""
import glob
import os

OPS_LINE = "XLA Ops"
SHORT_GAP_S = 100e-6        # gaps under this are summed, not named one by one
OP_NAME_CHARS = 96          # the TPU names an operation by its whole HLO line


def merge(intervals) -> list:
    """Sorted, disjoint cover of possibly overlapping (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def union_seconds(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def latest_xplane(profile_dir: str) -> str:
    runs = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not runs:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return runs[-1]


def read_planes(profile, window_name: str, span_names,
                platform: str) -> dict:
    """One pass over a ``ProfileData``: per-device operation events, the
    host annotations named in ``span_names``, and the window annotation.
    Times in seconds on the trace's clock.  ``platform`` is what JAX said
    the run's devices are: only ``cpu`` may lack a device plane."""
    devices, host_spans, windows, cpu_ops, lines_seen = {}, [], [], [], {}
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:")
        is_host = plane.name.startswith("/host:CPU")
        if not (is_device or is_host):
            continue
        lines = list(plane.lines)
        lines_seen[plane.name] = [ln.name for ln in lines][:12]
        if is_device:
            if not any(ln.name == OPS_LINE for ln in lines):
                continue
            ops = devices.setdefault(plane.name, [])
            for ln in lines:
                if ln.name != OPS_LINE:
                    continue
                for ev in ln.events:
                    s = ev.start_ns * 1e-9
                    ops.append((s, s + ev.duration_ns * 1e-9,
                                ev.name[:OP_NAME_CHARS]))
            continue
        for ln in lines:
            xla_thread = ln.name.startswith("tf_XLA")
            for ev in ln.events:
                name = ev.name
                if name == window_name or name in span_names:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    (windows if name == window_name
                     else host_spans).append((s, e, name))
                elif xla_thread and ev.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in ev.stats):
                    s = ev.start_ns * 1e-9
                    cpu_ops.append((s, s + ev.duration_ns * 1e-9, name))
    if not devices and platform != "cpu":
        raise RuntimeError(
            f"the run's platform is {platform!r} and the trace has no "
            f"/device: plane with an {OPS_LINE!r} line; planes and lines: "
            f"{lines_seen}")
    if not devices and cpu_ops:
        devices["/host:CPU (rehearsal)"] = cpu_ops
    return {"devices": devices, "host_spans": host_spans,
            "windows": windows, "lines_seen": lines_seen}


def name_gap(gap, host_spans) -> str:
    """The host annotation whose intervals cover most of the gap, if they
    cover half of it or more; else ``host_other``."""
    lo, hi = gap
    by_name = {}
    for s, e, name in host_spans:
        if e > lo and s < hi:
            by_name.setdefault(name, []).append((max(s, lo), min(e, hi)))
    best, cover = "host_other", 0.0
    for name, ivs in sorted(by_name.items()):
        c = union_seconds(ivs)
        if c > cover:
            best, cover = name, c
    return best if cover >= 0.5 * (hi - lo) else "host_other"


def digest_planes(planes: dict) -> dict:
    """``device_ops``: the ten operations with most time; ``idle_gaps``: up
    to five totals by name, then the five longest single gaps (the result
    line's ``breakdown`` may hold ten entries in each list)."""
    if len(planes["windows"]) != 1:
        raise RuntimeError(
            f"expected one window annotation in the trace, found "
            f"{len(planes['windows'])}; lines: {planes['lines_seen']}")
    if not planes["devices"]:
        raise RuntimeError("no operation ran on a device; lines: "
                           f"{planes['lines_seen']}")
    w0, w1, _ = planes["windows"][0]
    busy, op_seconds, gaps = [], {}, []
    for ops in planes["devices"].values():
        ivs = clip([(s, e) for s, e, _ in ops], w0, w1)
        cover = merge(ivs)
        busy.append(sum(e - s for s, e in cover))
        for s, e, name in ops:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + d
        edges = [w0] + [t for iv in cover for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(planes["devices"])
    busy_s = sum(busy) / n
    if busy_s <= 0:
        raise RuntimeError("no device operation inside the window")
    idle_by_name = {}
    named = []
    for lo, hi in gaps:
        if hi - lo < SHORT_GAP_S:
            name = "under_100us"
        else:
            name = name_gap((lo, hi), planes["host_spans"])
            named.append((hi - lo, name))
        idle_by_name[name] = idle_by_name.get(name, 0.0) + (hi - lo) / n
    # nested operations (a while loop and its body) would count twice in a
    # sum by name; the union above is what busy_s is
    device_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    idle = ([[f"sum:{k}", v] for k, v in
             sorted(idle_by_name.items(), key=lambda kv: -kv[1])[:5]]
            + [[name, d] for d, name in sorted(named, reverse=True)[:5]])
    return {
        "busy_s": busy_s, "window_s": w1 - w0, "devices": n,
        "device_ops": [[k, v / n] for k, v in device_ops],
        "idle_gaps": idle,
        "host_spans": planes["host_spans"], "window": (w0, w1),
        "lines_seen": planes["lines_seen"],
    }


def digest(profile_dir: str, window_name: str, span_names,
           platform: str) -> dict:
    import jax.profiler
    profile = jax.profiler.ProfileData.from_file(latest_xplane(profile_dir))
    return digest_planes(read_planes(profile, window_name, set(span_names),
                                     platform))


def span_share_pct(spans, name: str, lo: float, hi: float):
    """Share of [lo, hi] covered by the union of the spans called ``name``
    (``(name, start, end)`` on one clock), in percent; None where the span
    was never recorded."""
    ivs = clip([(s, e) for n, s, e in spans if n == name], lo, hi)
    if not ivs or hi <= lo:
        return None
    return 100.0 * union_seconds(ivs) / (hi - lo)
