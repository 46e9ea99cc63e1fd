"""Remote commands: name -> handler registry invocable over RPC.

The rDSN `register_command` surface (SURVEY.md §2.4 'Remote commands';
reference src/server/main.cpp:74-90 registers server-info/server-stat, the
shell invokes them via `remote_command`, src/shell/commands/misc.cpp). The
perf-counter scrape commands mirror command_helper.h:891-1146.
"""

import json
import time
from dataclasses import dataclass, field
from typing import List

from ..rpc import codec
from .perf_counters import counters

VERSION = "pegasus-tpu 2.0"
_START_TIME = time.time()


@dataclass
class RemoteCommandRequest:
    command: str = ""
    arguments: List[str] = field(default_factory=list)


@dataclass
class RemoteCommandResponse:
    output: str = ""


class RemoteCommandService:
    def __init__(self):
        self._commands = {}

    def register(self, name: str, fn) -> None:
        """fn(args: list[str]) -> str."""
        self._commands[name] = fn

    def register_defaults(self, node_kind: str, describe=None) -> None:
        self.register("help", lambda a: "\n".join(sorted(self._commands)))
        self.register("server-info", lambda a: (
            f"{VERSION}, {node_kind}, started {int(time.time() - _START_TIME)}s ago"))
        self.register("server-stat", self._cmd_server_stat)
        self.register("perf-counters", lambda a: self._dump_counters(None))
        self.register("perf-counters-by-prefix",
                      lambda a: self._dump_counters(
                          lambda n: any(n.startswith(p) for p in a)))
        self.register("perf-counters-by-substr",
                      lambda a: self._dump_counters(
                          lambda n: any(p in n for p in a)))
        self.register("set-fail-point", self._cmd_set_fail_point)
        self.register("events-dump", self._cmd_events_dump)
        self.register("metrics-history", self._cmd_metrics_history)
        self.register("compact-trace-dump", self._cmd_compact_trace_dump)
        self.register("device-health", self._cmd_device_health)
        self.register("request-trace-dump", self._cmd_request_trace_dump)
        self.register("slow-requests", self._cmd_slow_requests)
        self.register("profile-start", self._cmd_profile_start)
        self.register("profile-stop", self._cmd_profile_stop)
        self.register("job-trace", self._cmd_job_trace)
        self.register("table-stats", self._cmd_table_stats)
        self.register("slo-status", self._cmd_slo_status)
        if describe is not None:
            self.register("describe", lambda a: json.dumps(describe(), indent=1))

    @staticmethod
    def _cmd_set_fail_point(args) -> str:
        """set-fail-point <name> <action> — arm (or heal, with 'off()') a
        fail point in THIS server process at runtime, using the same
        action mini-language tests use (`sleep(ms)`, `raise(msg)`,
        `return(v)`, `N%`/`K*` modifiers). The chaos scenario engine's
        fault-injection surface (ISSUE 11): before this command, fail
        points could only be armed in-process before startup, so a
        spawned group worker or remote node was out of reach. Arming
        never clears other armed points (fail_points.arm). The reply is
        a JSON dict keyed by this process's pid, so a partition-group
        router's structural fan-out merge keeps every worker's ack and
        the caller can count how many processes armed."""
        import os

        from . import fail_points

        if len(args) < 2:
            return "usage: set-fail-point <name> <action>"
        name, action = args[0], " ".join(args[1:])
        try:
            fail_points.arm(name, action)
        except ValueError as e:
            return str(e)   # "bad fail point action: ..."
        return json.dumps({f"pid:{os.getpid()}": f"{name}={action}"})

    @staticmethod
    def _cmd_events_dump(args) -> str:
        """events-dump [last] [prefix] — this process's structured event
        ring (runtime/events.py), the flight recorder's per-node source.
        The reply is a JSON dict keyed by this process's pid, so a
        partition-group router's structural fan-out merge keeps EVERY
        worker process's ring side by side (disjoint keys survive the
        merge — the same shape set-fail-point uses for its acks)."""
        import os

        from .events import EVENTS

        last = int(args[0]) if args else None
        prefix = args[1] if len(args) > 1 else None
        return json.dumps({f"pid:{os.getpid()}":
                           EVENTS.snapshot(last=last, prefix=prefix)})

    @staticmethod
    def _cmd_metrics_history(args) -> str:
        """metrics-history [seconds] [prefix] — this process's metric
        history window (runtime/metric_history.py): the sampled tail of
        the selected counter series. Pid-keyed like events-dump so a
        grouped node's router merge keeps each worker's ring."""
        import os

        from .metric_history import HISTORY

        seconds = float(args[0]) if args else None
        prefix = args[1] if len(args) > 1 else None
        return json.dumps({f"pid:{os.getpid()}":
                           HISTORY.window(seconds=seconds, prefix=prefix)})

    @staticmethod
    def _cmd_compact_trace_dump(args) -> str:
        """compact-trace-dump [last] — recent compaction stage spans from
        the process-wide ring buffer (runtime/tracing.py)."""
        from .tracing import COMPACT_TRACER

        return COMPACT_TRACER.dump(int(args[0]) if args else 100)

    @staticmethod
    def _cmd_device_health(args) -> str:
        """device-health — the device watchdog's liveness/wedge state."""
        from ..ops.device_watchdog import WATCHDOG

        return json.dumps(WATCHDOG.state(), indent=1)

    @staticmethod
    def _cmd_request_trace_dump(args) -> str:
        """request-trace-dump [last] — recent sampled request traces from
        the serving-path tracer (runtime/tracing.py RequestTracer)."""
        from .tracing import REQUEST_TRACER

        return json.dumps(
            REQUEST_TRACER.trace(int(args[0]) if args else 50), indent=1)

    @staticmethod
    def _cmd_slow_requests(args) -> str:
        """slow-requests [last] — the slow-request ledger: full stage
        timelines of every request over the slow threshold."""
        from .tracing import REQUEST_TRACER

        return json.dumps(
            REQUEST_TRACER.slow_requests(int(args[0]) if args else 50),
            indent=1)

    @staticmethod
    def _cmd_profile_start(args) -> str:
        """profile-start <dir> — start jax's profiler in THIS process (the
        one that holds the chip): device lines plus the host's TraceMe
        spans, among them every open span of runtime/tracing.py as
        `pegasus:<name>`. The Python tracer stays off (it slows the host
        and swells the trace). View <dir> with xprof / Perfetto."""
        if len(args) != 1:
            return "usage: profile-start <dir>"
        import sys

        if "jax" not in sys.modules:
            return "no profile: this process has not loaded jax " \
                   "(no device backend is open here)"
        import jax

        from .tracing import annotate_requests

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        try:
            jax.profiler.start_trace(args[0], profiler_options=opts)
        except Exception as e:  # noqa: BLE001 - e.g. a profile already runs
            return f"profile-start failed: {e!r}"
        annotate_requests(True)   # request spans join the stage spans
        return f"profiling into {args[0]}"

    @staticmethod
    def _cmd_profile_stop(args) -> str:
        """profile-stop — stop the profile profile-start began and write
        it out (takes as long as the trace is large)."""
        import sys

        if "jax" not in sys.modules:
            return "no profile: this process has not loaded jax"
        import jax

        from .tracing import annotate_requests

        annotate_requests(False)
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - e.g. no profile runs
            return f"profile-stop failed: {e!r}"
        return "profile written"

    @staticmethod
    def _cmd_job_trace(args) -> str:
        """job-trace [last | <job-id>] — this process's background-job
        timelines (runtime/job_trace.py): completed jobs plus the still-
        open ones, or ONE timeline when a j…-id is given. Pid-keyed like
        events-dump, so a partition-group router's structural fan-out
        merge keeps every worker process's view side by side."""
        import os

        from .job_trace import JOB_TRACER

        if args and args[0].startswith("j"):
            found = JOB_TRACER.find(args[0])
            return json.dumps({f"pid:{os.getpid()}":
                               [found] if found else []})
        last = int(args[0]) if args else 50
        return json.dumps({f"pid:{os.getpid()}": JOB_TRACER.jobs(last=last)})

    @staticmethod
    def _cmd_table_stats(args) -> str:
        """table-stats — this process's per-table tenant ledger totals
        (runtime/table_stats.py). Pid-keyed like events-dump, so a
        partition-group router's structural fan-out merge keeps every
        worker process's fragment; callers fold them with
        table_stats.fold_snapshots (totals sum, percentiles MAX)."""
        import os

        from .table_stats import TABLE_STATS

        return json.dumps({f"pid:{os.getpid()}": TABLE_STATS.snapshot()})

    @staticmethod
    def _cmd_slo_status(args) -> str:
        """slo-status — the most recent per-table SLO burn-rate verdicts
        this process has computed ({} on nodes that never evaluate SLOs
        — the collector is the evaluator). Pid-keyed for the router
        merge like every other structural command."""
        import os

        from ..collector.info_collector import latest_slo

        return json.dumps({f"pid:{os.getpid()}": latest_slo()})

    def _cmd_server_stat(self, args) -> str:
        """One-line digest of selected counters (brief_stat.cpp role)."""
        snap = counters.snapshot()
        keys = sorted(k for k in snap if k.endswith("_qps"))[:8]
        parts = [f"{k.rsplit('.', 1)[-1]}={snap[k]:.0f}" for k in keys]
        return ", ".join(parts) if parts else "no stats yet"

    def _dump_counters(self, pred) -> str:
        snap = counters.snapshot()
        out = {k: v for k, v in sorted(snap.items()) if pred is None or pred(k)}
        return json.dumps(out, indent=1)

    def invoke(self, command: str, arguments: list) -> str:
        fn = self._commands.get(command)
        if fn is None:
            return f"unknown command: {command!r} (try 'help')"
        try:
            return fn(list(arguments))
        except Exception as e:  # surface the error text, keep serving
            return f"command failed: {e!r}"

    def rpc_handler(self, header, body) -> bytes:
        req = codec.decode(RemoteCommandRequest, body)
        return codec.encode(RemoteCommandResponse(
            self.invoke(req.command, req.arguments)))
