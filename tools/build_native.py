"""Build-freshness gate for the native libraries (ISSUE 20).

The runtime loaders (pegasus_tpu/native/__init__.py) rebuild lazily on
an mtime check, but only at FIRST use in a process — a test session that
imports the cached .so via an already-running server process, or a
source edit racing an import, can silently exercise a stale binary.
`ensure()` makes staleness impossible at one choke point: it compares
each native source against its artifact and rebuilds with the plain
in-image compiler (no pip, no setup.py) BEFORE anything imports
pegasus_tpu. tests/conftest.py calls it at collection time, so tier-1
always runs against the current C.

A missing compiler degrades LOUDLY to the pure-Python twins (the
loaders return None and every native call site has a byte-identical
fallback) — the message names what was skipped so a "why is it slow"
hunt starts in the right place.
"""

import os
import subprocess
import sys
import sysconfig

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    os.pardir, "pegasus_tpu", "native")
_DIR = os.path.abspath(_DIR)


def _ext_suffix() -> str:
    return sysconfig.get_config_var("EXT_SUFFIX") or ".so"


def _targets() -> list:
    """[(label, source, artifact, build argv), ...] for every native lib."""
    inc = sysconfig.get_paths()["include"]
    fc_src = os.path.join(_DIR, "fastcodec.c")
    fc_so = os.path.join(_DIR, "fastcodec" + _ext_suffix())
    ho_src = os.path.join(_DIR, "hostops.cpp")
    ho_so = os.path.join(_DIR, "libhostops.so")
    return [
        ("fastcodec", fc_src, fc_so,
         ["gcc", "-O2", "-shared", "-fPIC", f"-I{inc}"]),
        ("hostops", ho_src, ho_so,
         ["g++", "-O3", "-shared", "-fPIC"]),
    ]


def _build(src: str, out: str, cc: list) -> str:
    """Atomic rebuild (tmp + os.replace, same discipline as the runtime
    loaders: a crashed compiler must never leave a corrupt artifact that
    is fresher than its source). -> status string."""
    tmp = f"{out}.{os.getpid()}.tmp"

    def drop_tmp():
        try:
            os.unlink(tmp)
        except OSError:
            pass

    try:
        res = subprocess.run(cc + ["-o", tmp, src], capture_output=True,
                             timeout=180)
    except FileNotFoundError:
        return "missing-compiler"
    except (OSError, subprocess.TimeoutExpired):
        drop_tmp()
        return "build-failed"
    if res.returncode != 0:
        drop_tmp()
        sys.stderr.write(res.stderr.decode(errors="replace")[-2000:] + "\n")
        return "build-failed"
    try:
        os.replace(tmp, out)
    except OSError:
        drop_tmp()
        return "build-failed"
    return "rebuilt"


def ensure(quiet: bool = False) -> dict:
    """Rebuild every stale native artifact. -> {label: status} with
    status in {fresh, rebuilt, missing-compiler, build-failed,
    missing-source}. Never raises: any failure means the pure-Python
    twins serve (loudly, unless quiet)."""
    statuses = {}
    for label, src, out, cc in _targets():
        if not os.path.exists(src):
            statuses[label] = "missing-source"
            continue
        try:
            fresh = (os.path.exists(out)
                     and os.path.getmtime(out) >= os.path.getmtime(src))
        except OSError:
            fresh = False
        if fresh:
            statuses[label] = "fresh"
            continue
        statuses[label] = _build(src, out, cc)
        if statuses[label] in ("missing-compiler", "build-failed") \
                and not quiet:
            print(f"[build-native] {label}: {statuses[label]} — the "
                  f"PURE-PYTHON fallback will serve (slower, "
                  f"byte-identical); fix the toolchain to re-enable the "
                  f"native path", file=sys.stderr, flush=True)
    return statuses


def main() -> int:
    statuses = ensure()
    for label, status in sorted(statuses.items()):
        print(f"{label}: {status}")
    bad = [s for s in statuses.values()
           if s in ("build-failed", "missing-source")]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
