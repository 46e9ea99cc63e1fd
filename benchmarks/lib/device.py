"""The device as jax reports it, the refusal to measure on anything but
the chips the cell asks for, and the profiler switch."""

import contextlib
import json
import os


GUARD_TOTALS = ("fallbacks", "retries", "deadline_abandons", "breaker_trips",
                "device_failures", "compile_wait_timeouts", "compile_behind")


def still_totals(lanes: list, compile_report: dict) -> dict:
    """What must stand still inside a window, as two sums: every total of
    the given lane guards' states, and every program the process has
    compiled, failed to compile or is compiling."""
    return {"guard": sum(lane[k] for lane in lanes for k in GUARD_TOTALS),
            "compiles": sum(compile_report[k]
                            for k in ("compiled", "failed", "inflight"))}


def identity(ctx) -> dict:
    """-> {platform, kind, count}. Raises unless this is a TPU host with
    at least the cell's chips, or a rehearsal."""
    import jax

    devs = jax.devices()
    ident = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if not ctx.rehearsal:
        if ident["platform"] != "tpu":
            raise RuntimeError(f"no TPU: jax resolved {ident} (use "
                               f"--rehearsal for a cpu run)")
        if ident["count"] < ctx.cell["chips"]:
            raise RuntimeError(f"the cell asks for {ctx.cell['chips']} "
                               f"chips, jax sees {ident['count']}")
    return ident


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no such count: the cpu of a rehearsal)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def peaks(kind: str) -> dict:
    """The published peaks of this device kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in lib/peaks.json")
    return table[kind]


def span(name: str):
    """A host span on the profiler's clock (nothing when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


@contextlib.contextmanager
def profiled(log_dir: str, on: bool):
    """Trace what runs inside with jax's profiler: device events and the
    host's TraceMe spans; the Python tracer stays off (it slows the host
    and swells the trace)."""
    if not on:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
