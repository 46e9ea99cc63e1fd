"""Base utilities (src/base/pegasus_utils.{h,cpp})."""

import os
import threading
import time


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where jax's persistent compile cache lives: wherever
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache — always a
    fixed path, because the path is part of the cache key and a directory
    that moves never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache — the sort/merge
    networks compile per shape-set and every process (servers, group
    workers, benches, tests) reuses them. A directory placed from outside
    is left to jax, which reads JAX_COMPILATION_CACHE_DIR itself; nothing
    here overrides it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


_DEVICE_LOCK = threading.Lock()
_DEVICE = None  # identity of the platform backend="tpu" resolved to


def open_device_backend() -> dict:
    """The one gate every backend="tpu" process passes before its first
    device kernel: enable the compile cache, resolve jax's platform ONCE,
    and refuse unless it is a TPU — or JAX_PLATFORMS explicitly asks for
    another platform first (how tests and CPU rehearsals run the same
    kernels on XLA:CPU; "tpu,cpu" still asks for the TPU). Without this a
    host where libtpu finds no chip would run every "device" kernel on
    the CPU and say nothing. -> the identity dict device-health reports
    (platform, device_kind, device_count, jax and libtpu versions)."""
    global _DEVICE
    with _DEVICE_LOCK:
        if _DEVICE is not None:
            return _DEVICE
        enable_compile_cache()
        import jax

        devs = jax.devices()
        ident = {"platform": devs[0].platform,
                 "device_kind": devs[0].device_kind,
                 "device_count": len(devs),
                 "jax": jax.__version__,
                 "libtpu": _libtpu_version()}
        explicit = os.environ.get("JAX_PLATFORMS", "")
        asked = explicit.split(",")[0].strip()
        if ident["platform"] != "tpu" and asked in ("", "tpu"):
            raise RuntimeError(
                f"backend=\"tpu\" needs a TPU, but jax resolved platform "
                f"{ident['platform']!r} ({ident['device_kind']} x"
                f"{ident['device_count']}) with JAX_PLATFORMS="
                f"{explicit or 'unset'}; set JAX_PLATFORMS=cpu explicitly "
                f"to run the device kernels on XLA:CPU")
        _DEVICE = ident
        return ident


def device_report() -> dict:
    """What every health surface says about the device without reaching
    into the process: `device` (what open_device_backend resolved),
    `device_memory` (bytes in use / peak / limit as the first device's
    allocator reports them: the headroom under HBM residency) and
    `compile_cache_dir` (where this process keeps jax's persistent
    compile cache). All None in a process that never opened a
    tpu-backend engine — a cpu-backend server never imports jax."""
    if _DEVICE is None:
        return {"device": None, "device_memory": None,
                "compile_cache_dir": None}
    import jax

    stats = jax.devices()[0].memory_stats()
    return {
        "device": _DEVICE,
        "device_memory": stats and {
            k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")},
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _libtpu_version():
    from importlib.metadata import PackageNotFoundError, version

    try:
        return version("libtpu")
    except PackageNotFoundError:
        return None


# TTL timestamps are seconds since 2016-01-01 00:00:00 GMT
# (src/base/pegasus_utils.h:34-36)
epoch_begin = 1451606400


def epoch_now(now: float = None) -> int:
    """Seconds since the 2016 epoch; the expire_ts clock."""
    return int(now if now is not None else time.time()) - epoch_begin


_PRINTABLE = set(range(0x20, 0x7F)) - {ord('"'), ord("\\")}


def c_escape_string(data: bytes, always_escape: bool = False) -> str:
    """C-style escaping for log/shell display (src/base/pegasus_utils.h)."""
    out = []
    for b in data:
        if not always_escape and b in _PRINTABLE:
            out.append(chr(b))
        elif b == ord('"') and not always_escape:
            out.append('\\"')
        elif b == ord("\\") and not always_escape:
            out.append("\\\\")
        else:
            out.append(f"\\x{b:02X}")
    return "".join(out)


def c_unescape_string(s: str) -> bytes:
    """Inverse of c_escape_string for shell input."""
    out = bytearray()
    i = 0
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            n = s[i + 1]
            if n == "x" and i + 3 < len(s):
                out.append(int(s[i + 2 : i + 4], 16))
                i += 4
                continue
            if n in ('"', "\\"):
                out.append(ord(n))
                i += 2
                continue
        out.append(ord(c))
        i += 1
    return bytes(out)
