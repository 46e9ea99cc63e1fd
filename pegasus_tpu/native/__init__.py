"""ctypes bindings for the native host-ops library (hostops.cpp).

Compiles the shared library on first use with the in-image g++ (no pip, no
pybind11 — plain `extern "C"` + ctypes, the SURVEY §2 requirement that
runtime hot paths be native like the reference's C++). Every binding has a
numpy fallback; `available()` reports whether the native path is active.
"""

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "hostops.cpp")
_SO = os.path.join(_DIR, "libhostops.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> bool:
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", _SO, _SRC]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
        return res.returncode == 0
    except (OSError, subprocess.TimeoutExpired):
        return False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            # a checkout gives the committed .so and its source arbitrary
            # mtimes: with no compiler the shipped library still loads,
            # and _bind below refuses it if it predates a symbol
            if not _build() and not os.path.exists(_SO):
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        try:
            _bind(lib)
        except AttributeError:
            # a stale prebuilt .so that predates a symbol (mtime passed on
            # clock skew / shipped artifact): rebuild once, else degrade to
            # the numpy fallbacks instead of crashing available(). The
            # rebuilt library must load from a UNIQUE path — dlopen dedupes
            # by pathname, so re-CDLLing _SO would return the stale handle
            if not _build():
                return None
            import shutil
            import tempfile

            tmp = tempfile.NamedTemporaryFile(prefix="libhostops_",
                                              suffix=".so", delete=False)
            tmp.close()
            try:
                shutil.copy(_SO, tmp.name)
                lib = ctypes.CDLL(tmp.name)
                _bind(lib)
            except (OSError, AttributeError):
                return None
        _lib = lib
        return _lib


def _bind(lib) -> None:
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C")
    u64p = np.ctypeslib.ndpointer(np.uint64, flags="C")
    lib.crc64_batch.argtypes = [u8p, i64p, i64p, ctypes.c_int64, u64p]
    lib.gather_arena.argtypes = [u8p, i64p, i32p, i64p, ctypes.c_int64,
                                 u8p, i64p]
    lib.pack_prefixes.argtypes = [u8p, i64p, i32p, ctypes.c_int64,
                                  ctypes.c_int32, u32p]
    lib.merge_counts.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                                 ctypes.c_int64, ctypes.c_int32, i64p]
    boolp = np.ctypeslib.ndpointer(np.bool_, flags="C")
    ptrs = ctypes.POINTER(ctypes.c_void_p)   # K base pointers, one a run
    lib.gather_block_runs_uniform.argtypes = [
        ptrs, ctypes.c_int64, ptrs, ctypes.c_int64, ptrs, ptrs, ptrs,
        i64p, ctypes.c_int32, i32p, ctypes.c_int64,
        u8p, u8p, u32p, u32p, boolp]
    lib.gather_keys_runs_uniform.argtypes = [
        ptrs, ctypes.c_int64, ptrs, ptrs, ptrs,
        i64p, ctypes.c_int32, i32p, ctypes.c_int64,
        u8p, u32p, u32p, boolp]


def available() -> bool:
    return _load() is not None


def native_on() -> bool:
    """The one knob for the native read data plane (ISSUE 20):
    ``PEGASUS_NATIVE=0`` forces the byte-identical pure-Python twins for
    frame dispatch, vectored reply writes, and mmap SST reads. Read live
    per call (not cached) so a test or bench A/B can flip it in-process
    between connections."""
    return os.environ.get("PEGASUS_NATIVE", "1") != "0"


# ------------------------------------------------------------- fastcodec
# The RPC wire codec's C interpreter (fastcodec.c): a true CPython
# extension (needs Python.h, unlike hostops' plain ctypes), compiled on
# first use and imported from its file path. rpc.codec falls back to the
# pure-Python closures when this returns None.

_FC_SRC = os.path.join(_DIR, "fastcodec.c")
_fc_lock = threading.Lock()
_fc_mod = None
_fc_tried = False


def fastcodec():
    """-> the compiled fastcodec extension module, or None."""
    global _fc_mod, _fc_tried
    with _fc_lock:
        if _fc_tried:
            return _fc_mod
        _fc_tried = True
        import sysconfig

        suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
        so = os.path.join(_DIR, "fastcodec" + suffix)

        def try_load(path):
            try:
                import importlib.util

                spec = importlib.util.spec_from_file_location("fastcodec",
                                                              path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod
            except Exception:  # noqa: BLE001 - load failure -> rebuild/None
                return None

        mod = None
        try:
            fresh = (os.path.exists(so)
                     and os.path.getmtime(so) >= os.path.getmtime(_FC_SRC))
        except OSError:  # e.g. source missing but artifact present:
            fresh = os.path.exists(so)  # trust the artifact, else None
        if fresh:
            mod = try_load(so)
        if mod is None and not os.path.exists(_FC_SRC):
            return None  # nothing to build from
        if mod is None:
            # build to a per-process tmp then atomically replace: several
            # server processes may race the first build, and gcc writing
            # the final path directly could leave a corrupt (and
            # fresher-than-source, so never rebuilt) artifact
            tmp = f"{so}.{os.getpid()}.tmp"
            inc = sysconfig.get_paths()["include"]
            cmd = ["gcc", "-O2", "-shared", "-fPIC", f"-I{inc}",
                   "-o", tmp, _FC_SRC]
            try:
                res = subprocess.run(cmd, capture_output=True, timeout=120)
                if res.returncode != 0:
                    return None
                os.replace(tmp, so)
            except (OSError, subprocess.TimeoutExpired):
                return None
            finally:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            mod = try_load(so)
        _fc_mod = mod
        return _fc_mod


def crc64_batch(arena, offsets, lengths):
    """uint64[n] crc64 of each slice; native slice-by-8 when available."""
    lib = _load()
    n = len(offsets)
    if lib is None or n == 0:
        from ..base.crc64 import crc64_batch_numpy

        return crc64_batch_numpy(arena, offsets, lengths)
    out = np.empty(n, np.uint64)
    lib.crc64_batch(np.ascontiguousarray(arena, np.uint8),
                    np.ascontiguousarray(offsets, np.int64),
                    np.ascontiguousarray(lengths, np.int64), n, out)
    return out


def gather_arena(arena, off, len32, idx):
    """-> (out_arena, out_off) compacted selection; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    idx = np.ascontiguousarray(idx, np.int64)
    len32 = np.ascontiguousarray(len32, np.int32)
    total = int(len32[idx].astype(np.int64).sum())
    out = np.empty(total, np.uint8)
    out_off = np.empty(len(idx), np.int64)
    lib.gather_arena(np.ascontiguousarray(arena, np.uint8),
                     np.ascontiguousarray(off, np.int64),
                     len32, idx, len(idx), out, out_off)
    return out, out_off


def pack_prefixes(arena, off, len32, w):
    """-> uint32[n, w] big-endian packed prefixes; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(off)
    out = np.empty((w, n), np.uint32)
    lib.pack_prefixes(np.ascontiguousarray(arena, np.uint8),
                      np.ascontiguousarray(off, np.int64),
                      np.ascontiguousarray(len32, np.int32), n, w,
                      out.reshape(-1))
    return out.T


def _columns(runs, name: str, dtype) -> list:
    return [np.ascontiguousarray(getattr(r, name), dtype) for r in runs]


def _bases(arrs: list):
    """The arrays' base addresses as a C array (the caller keeps arrs)."""
    return (ctypes.c_void_p * len(arrs))(*[a.ctypes.data for a in arrs])


def gather_runs_uniform(runs, klen, vlen, idx, out_keys, out_vals,
                        out_expire, out_hash32, out_deleted,
                        use_native: bool = True) -> None:
    """Rows idx of the runs AS IF concatenated, gathered into preallocated
    outputs without the concatenation: run r owns the indices
    [starts[r], starts[r+1]), starts = cumsum of the runs' row counts.
    Every run is a uniform-layout block (KVBlock.uniform_layout) of the
    one (klen, vlen). out_vals None gathers keys + aux only (the values
    come off the device). Native by-run loop when the library is there,
    else the numpy twin: one masked fancy-index per run.

    Device-derived indices feed unchecked native pointer arithmetic (and
    numpy fancy indexing would silently wrap a -1): a pipeline defect must
    be loud, not memory corruption."""
    starts = np.zeros(len(runs) + 1, np.int64)
    np.cumsum([r.n for r in runs], out=starts[1:])
    idx = np.asarray(idx)
    count, total = len(idx), int(starts[-1])
    if count and (int(idx.min()) < 0 or int(idx.max()) >= total):
        raise ValueError(
            "survivor index outside the runs' rows — device pipeline bug "
            f"(min {int(idx.min())}, max {int(idx.max())}, n {total})")
    idx = np.ascontiguousarray(idx, np.int32)
    lib = _load() if use_native else None
    if lib is None:
        for r, run in enumerate(runs):
            lo, hi = int(starts[r]), int(starts[r + 1])
            sel = (idx >= lo) & (idx < hi)
            local = idx[sel] - lo
            out_keys.reshape(count, klen)[sel] = \
                run.key_arena.reshape(run.n, klen)[local]
            if out_vals is not None:
                out_vals.reshape(count, vlen)[sel] = \
                    run.val_arena.reshape(run.n, vlen)[local]
            out_expire[sel] = run.expire_ts[local]
            out_hash32[sel] = run.hash32[local]
            out_deleted[sel] = run.deleted[local]
        return
    keys = _columns(runs, "key_arena", np.uint8)
    expires = _columns(runs, "expire_ts", np.uint32)
    hashes = _columns(runs, "hash32", np.uint32)
    deleteds = _columns(runs, "deleted", np.bool_)
    if out_vals is None:
        lib.gather_keys_runs_uniform(
            _bases(keys), int(klen), _bases(expires), _bases(hashes),
            _bases(deleteds), starts, len(runs), idx, count,
            out_keys.reshape(-1), out_expire, out_hash32, out_deleted)
        return
    vals = _columns(runs, "val_arena", np.uint8)
    lib.gather_block_runs_uniform(
        _bases(keys), int(klen), _bases(vals), int(vlen), _bases(expires),
        _bases(hashes), _bases(deleteds), starts, len(runs), idx, count,
        out_keys.reshape(-1), out_vals.reshape(-1), out_expire, out_hash32,
        out_deleted)


def merge_counts(a_sbytes, b_sbytes, side: str):
    """Counts of b-items < (side='left') / <= (side='right') each a-item.
    Both inputs ascending fixed-width byte arrays; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    a = np.ascontiguousarray(a_sbytes)
    b = np.ascontiguousarray(b_sbytes)
    out = np.empty(len(a), np.int64)
    lib.merge_counts(a.view(np.uint8).reshape(-1), len(a),
                     b.view(np.uint8).reshape(-1), len(b),
                     a.dtype.itemsize, 1 if side == "right" else 0, out)
    return out
