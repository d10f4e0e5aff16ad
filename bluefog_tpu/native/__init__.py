"""ctypes bindings for the native core (see ``src/bluefog_native.h``).

Loads ``libbluefog_tpu_native.so`` if built (``make -C bluefog_tpu/native``),
attempting a one-time build when a toolchain is available.  Everything has a
pure-Python fallback, so ``lib() is None`` is always a supported state — the
native layer is a performance/production feature (host-side schedule
compilation, timeline writer, DCN window transport), not a correctness one.

Staleness: a library older than any ``src/*.cc``/``*.h`` is rebuilt in place
before loading; when no toolchain is available the stale build is still
loaded (with a warning) but :func:`is_stale` reports it, and the window
transport's native fast path (``BLUEFOG_TPU_WIN_NATIVE``) auto-falls back to
the Python hot loop — old compiled code is never silently driven by new
Python expecting new symbols or struct layouts.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_HERE, "src")
_LIB_PATH = os.path.join(_HERE, "libbluefog_tpu_native.so")

_lib = None
_tried = False
_stale = False
_lock = threading.Lock()


class WinMsg(ctypes.Structure):
    _fields_ = [
        ("op", ctypes.c_uint8),
        ("src", ctypes.c_int32),
        ("dst", ctypes.c_int32),
        ("weight", ctypes.c_double),
        ("p_weight", ctypes.c_double),
        ("name", ctypes.c_char * 128),
        ("payload_len", ctypes.c_uint64),
    ]


class WinItem(ctypes.Structure):
    """Mirror of ``bf_win_item_t``: one ordered drain item — a raw message
    (kind 0) or a folded commit entry (kind 1)."""
    _fields_ = [
        ("kind", ctypes.c_uint8),
        ("op", ctypes.c_uint8),
        ("replace", ctypes.c_uint8),
        ("frame", ctypes.c_uint8),
        ("src", ctypes.c_int32),
        ("dst", ctypes.c_int32),
        ("puts", ctypes.c_int32),
        ("accs", ctypes.c_int32),
        ("weight", ctypes.c_double),
        ("p_weight", ctypes.c_double),
        ("off", ctypes.c_uint64),
        ("len", ctypes.c_uint64),
        ("wire_bytes", ctypes.c_uint64),
        # Wire trace tag of the last tagged message folded into a commit
        # entry (trace_seq == 0: untagged); raw items keep the trailer in
        # their payload instead.
        ("trace_seq", ctypes.c_uint32),
        ("trace_src", ctypes.c_int32),
        ("trace_mono_us", ctypes.c_int64),
        ("trace_unix_us", ctypes.c_int64),
        ("trace_step", ctypes.c_int64),
        ("name", ctypes.c_char * 128),
    ]


class RecEvent(ctypes.Structure):
    """Mirror of ``bf_rec_event_t`` (one flight-recorder ring slot)."""
    _fields_ = [
        ("t_us", ctypes.c_int64),
        ("src", ctypes.c_int32),
        ("dst", ctypes.c_int32),
        ("seq", ctypes.c_uint32),
        ("len", ctypes.c_uint32),
        ("etype", ctypes.c_uint8),
        ("op", ctypes.c_uint8),
        ("stripe", ctypes.c_uint8),
        ("flags", ctypes.c_uint8),
        ("name", ctypes.c_char * 20),
    ]


class WinRxStats(ctypes.Structure):
    """Mirror of ``bf_winrx_stats_t`` (cumulative native-drain counters)."""
    _fields_ = [
        ("batch_frames", ctypes.c_uint64),
        ("msgs", ctypes.c_uint64),
        ("folded_msgs", ctypes.c_uint64),
        ("commits", ctypes.c_uint64),
        ("bytes", ctypes.c_uint64),
        ("by_op", ctypes.c_uint64 * 16),
        ("batch_size_hist", ctypes.c_uint64 * 25),
        ("batch_size_sum", ctypes.c_double),
        ("decode_busy", ctypes.c_uint64),
        ("decode_threads", ctypes.c_uint64),
        ("decoded_frames", ctypes.c_uint64),
    ]


class WinTxStats(ctypes.Structure):
    """Mirror of ``bf_wintx_stats_t`` (cumulative native-sender counters)."""
    _fields_ = [
        ("msgs_enq", ctypes.c_uint64),
        ("msgs_done", ctypes.c_uint64),
        ("frames", ctypes.c_uint64),
        ("batches", ctypes.c_uint64),
        ("batched_msgs", ctypes.c_uint64),
        ("bytes", ctypes.c_uint64),
        ("errors", ctypes.c_uint64),
        ("retries", ctypes.c_uint64),
        ("dropped_msgs", ctypes.c_uint64),
        ("queue_len", ctypes.c_uint64),
        ("by_op", ctypes.c_uint64 * 16),
        ("batch_size_hist", ctypes.c_uint64 * 25),
        ("send_sec_hist", ctypes.c_uint64 * 25),
        ("batch_size_sum", ctypes.c_double),
        ("send_sec_sum", ctypes.c_double),
    ]


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i32, i64, u64, dbl = (ctypes.c_int32, ctypes.c_int64, ctypes.c_uint64,
                          ctypes.c_double)
    ptr = ctypes.POINTER
    lib.bf_rounds_from_matrix.restype = i32
    lib.bf_rounds_from_matrix.argtypes = [
        i32, ptr(dbl), ptr(i32), ptr(dbl), ptr(dbl), ptr(i32)]
    lib.bf_uniform_weights.restype = None
    lib.bf_uniform_weights.argtypes = [i32, ptr(dbl)]

    lib.bf_timeline_open.restype = ctypes.c_void_p
    lib.bf_timeline_open.argtypes = [ctypes.c_char_p, i32]
    lib.bf_timeline_event.restype = None
    lib.bf_timeline_event.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char,
        i64, i64, i64]
    lib.bf_timeline_dropped.restype = i64
    lib.bf_timeline_dropped.argtypes = [ctypes.c_void_p]
    lib.bf_timeline_close.restype = None
    lib.bf_timeline_close.argtypes = [ctypes.c_void_p]

    lib.bf_winsvc_start.restype = ctypes.c_void_p
    lib.bf_winsvc_start.argtypes = [i32, i32]
    lib.bf_winsvc_port.restype = i32
    lib.bf_winsvc_port.argtypes = [ctypes.c_void_p]
    lib.bf_winsvc_recv.restype = i32
    lib.bf_winsvc_recv.argtypes = [
        ctypes.c_void_p, ptr(WinMsg), ptr(ctypes.c_uint8), u64]
    lib.bf_winsvc_send.restype = i32
    lib.bf_winsvc_send.argtypes = [
        ctypes.c_char_p, i32, ctypes.c_uint8, ctypes.c_char_p, i32, i32,
        dbl, dbl, ptr(ctypes.c_uint8), u64]
    lib.bf_winsvc_stop.restype = None
    lib.bf_winsvc_stop.argtypes = [ctypes.c_void_p]

    # Window-transport native hot path (this PR's symbols).  An older .so
    # — stale build without a toolchain to refresh it — simply lacks them;
    # bind what exists and let has_win_native() report the capability.
    try:
        lib.bf_winsvc_win_set.restype = i32
        lib.bf_winsvc_win_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          i64]
        lib.bf_winsvc_drain.restype = i32
        lib.bf_winsvc_drain.argtypes = [
            ctypes.c_void_p, ptr(WinItem), i32, ptr(ctypes.c_uint8), u64,
            ptr(ctypes.c_float), u64, i32, i32]
        lib.bf_winsvc_rx_stats.restype = None
        lib.bf_winsvc_rx_stats.argtypes = [ctypes.c_void_p, ptr(WinRxStats)]
        lib.bf_winsvc_set_decode.restype = i32
        lib.bf_winsvc_set_decode.argtypes = [ctypes.c_void_p, i32]

        lib.bf_wintx_start.restype = ctypes.c_void_p
        lib.bf_wintx_start.argtypes = [u64, u64, i32, i32, dbl, i32]
        lib.bf_wintx_send.restype = i32
        # payload rides as c_void_p, which ctypes accepts as EITHER bytes
        # (small rows: tobytes() + the cheapest pointer conversion) or a
        # raw int address (large rows: the .ctypes pointer path — past
        # ~64 KiB the byte copy dwarfs the ~µs pointer extraction it was
        # avoiding; see transport._ctypes_payload).
        lib.bf_wintx_send.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i32, ctypes.c_uint8,
            ctypes.c_char_p, i32, i32, dbl, dbl, ctypes.c_void_p, u64, i32,
            i32]
        lib.bf_wintx_flush.restype = i32
        lib.bf_wintx_flush.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       i32, dbl]
        lib.bf_wintx_err_count.restype = i64
        lib.bf_wintx_err_count.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           i32]
        lib.bf_wintx_kick.restype = None
        lib.bf_wintx_kick.argtypes = [ctypes.c_void_p]
        lib.bf_wintx_drop_peer.restype = i64
        lib.bf_wintx_drop_peer.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           i32]
        lib.bf_wintx_set_partition.restype = None
        lib.bf_wintx_set_partition.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p]
        lib.bf_wintx_stats.restype = None
        lib.bf_wintx_stats.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       i32, ptr(WinTxStats)]
        lib.bf_wintx_stripe_stats.restype = None
        lib.bf_wintx_stripe_stats.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, i32, i32, ptr(WinTxStats)]
        lib.bf_wintx_stripes.restype = i32
        lib.bf_wintx_stripes.argtypes = [ctypes.c_void_p]
        lib.bf_wintx_stop.restype = None
        lib.bf_wintx_stop.argtypes = [ctypes.c_void_p]
    except AttributeError:
        pass
    # Wire trace tags + transport flight recorder (winsvc.cc, this PR's
    # symbols) — own try: an older .so missing them falls back cleanly
    # (has_win_native additionally requires bf_rec_snapshot, because the
    # same build grew bf_win_item_t's trace fields).
    try:
        lib.bf_trace_configure.restype = None
        lib.bf_trace_configure.argtypes = [i32]
        lib.bf_trace_period.restype = i32
        lib.bf_trace_period.argtypes = []
        lib.bf_trace_next.restype = i32
        lib.bf_trace_next.argtypes = [i32, ptr(ctypes.c_uint8)]
        lib.bf_trace_set_step.restype = None
        lib.bf_trace_set_step.argtypes = [i64]
        lib.bf_trace_step.restype = i64
        lib.bf_trace_step.argtypes = []
        lib.bf_winsvc_set_fold_across_put.restype = None
        lib.bf_winsvc_set_fold_across_put.argtypes = [i32]
        lib.bf_rec_enable.restype = i64
        lib.bf_rec_enable.argtypes = [i64]
        lib.bf_rec_is_enabled.restype = i32
        lib.bf_rec_is_enabled.argtypes = []
        lib.bf_rec_note.restype = None
        lib.bf_rec_note.argtypes = [i32, i32, i32, i32, i32,
                                    ctypes.c_uint32, u64, ctypes.c_char_p]
        lib.bf_rec_snapshot.restype = i64
        lib.bf_rec_snapshot.argtypes = [ptr(RecEvent), i64]
        lib.bf_rec_reset.restype = None
        lib.bf_rec_reset.argtypes = []
    except AttributeError:
        pass
    # Zero-copy XLA put plans (xlacall.cc, this PR's symbols) — bound in
    # their own try so an older .so missing them degrades to the PR-9
    # path alone (has_win_xla() reports the capability).
    try:
        lib.bf_xla_plan_new.restype = i64
        lib.bf_xla_plan_new.argtypes = [ctypes.c_char_p, i64, i32, i32, dbl]
        lib.bf_xla_plan_edge.restype = i32
        lib.bf_xla_plan_edge.argtypes = [
            i64, i32, ctypes.c_char_p, i32, ctypes.c_uint8, i32, i32, dbl,
            i64, i32]
        lib.bf_xla_plan_set_p.restype = i32
        lib.bf_xla_plan_set_p.argtypes = [i64, ptr(dbl), i32]
        # data rides as c_void_p: the dispatcher passes the RAW XLA buffer
        # pointer (an int) — the zero-copy contract of the whole path.
        lib.bf_xla_plan_run.restype = i32
        lib.bf_xla_plan_run.argtypes = [i64, ctypes.c_void_p,
                                        ctypes.c_void_p, u64]
        lib.bf_xla_plan_free.restype = i32
        lib.bf_xla_plan_free.argtypes = [i64]
        lib.bf_xla_drop_residuals.restype = None
        lib.bf_xla_drop_residuals.argtypes = [ctypes.c_char_p]
        lib.bf_xla_take_residual.restype = i64
        lib.bf_xla_take_residual.argtypes = [ctypes.c_char_p, i32, i32,
                                             ptr(ctypes.c_float), i64]
        lib.bf_xla_add_residual.restype = i32
        lib.bf_xla_add_residual.argtypes = [ctypes.c_char_p, i32, i32,
                                            ptr(ctypes.c_float), i64]
        lib.bf_xla_has_handler.restype = i32
        lib.bf_xla_has_handler.argtypes = []
    except AttributeError:
        pass
    return lib


def _fastcall_artifact() -> Optional[str]:
    """Path of the built ``_bf_fastcall`` extension module, if any."""
    try:
        for fn in os.listdir(_HERE):
            if fn.startswith("_bf_fastcall") and fn.endswith(".so"):
                return os.path.join(_HERE, fn)
    except OSError:
        pass
    return None


def _stale_sources(lib_path: str = _LIB_PATH,
                   src_dir: str = _SRC_DIR) -> List[str]:
    """Source files newer than their built artifact (empty list = fresh).

    Pure mtime comparison over ``src/*.cc`` / ``src/*.h`` — the same
    staleness rule the Makefile's dependency graph encodes, applied at
    LOAD time so an edited native source can never be silently shadowed
    by an old compiled artifact.  ``fastcall.cc`` is judged against the
    ``_bf_fastcall`` module (its artifact); on hosts without Python.h the
    module legitimately does not exist and fastcall.cc is ignored."""
    try:
        lib_mtime = os.path.getmtime(lib_path)
    except OSError:
        return []
    fast = _fastcall_artifact() if src_dir == _SRC_DIR else None
    try:
        fast_mtime = os.path.getmtime(fast) if fast else None
    except OSError:
        fast_mtime = None
    out = []
    try:
        entries = sorted(os.listdir(src_dir))
    except OSError:
        return []
    for fn in entries:
        if not (fn.endswith(".cc") or fn.endswith(".h")):
            continue
        ref = lib_mtime
        if fn == "fastcall.cc":
            if fast_mtime is None:
                continue
            ref = fast_mtime
        try:
            if os.path.getmtime(os.path.join(src_dir, fn)) > ref:
                out.append(fn)
        except OSError:
            continue
    return out


def build(force: bool = False) -> bool:
    """Compile the native library in place; returns success.  Without
    ``force`` the Makefile's own dependency graph decides what (if
    anything) recompiles, so calling this on a fresh tree is a no-op."""
    if os.path.exists(_LIB_PATH) and not force and not _stale_sources():
        return True
    try:
        subprocess.run(["make", "-C", _HERE, "-s"] + (["-B"] if force else []),
                       check=True, capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH) and not _stale_sources()
    except (subprocess.SubprocessError, FileNotFoundError):
        return False


def lib(auto_build: bool = True) -> Optional[ctypes.CDLL]:
    """The loaded native library, or None when unavailable.

    A stale library (any ``src/*.cc``/``.h`` newer than the ``.so``) is
    rebuilt before loading; if the rebuild fails (no toolchain) the stale
    build is loaded with a warning and :func:`is_stale` flips — consumers
    with layout-sensitive fast paths (the window transport) check it and
    fall back to their Python implementations."""
    global _lib, _tried, _stale
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        allow_build = (auto_build and
                       os.environ.get("BLUEFOG_TPU_NO_NATIVE") != "1")
        if not os.path.exists(_LIB_PATH):
            if not (allow_build and build()):
                return None
        else:
            stale = _stale_sources()  # one scan: condition AND warning
            if stale and not (allow_build and build()):
                _stale = True
                import logging
                logging.getLogger("bluefog_tpu").warning(
                    "native core is STALE (%s newer than the built "
                    "library) and could not be rebuilt — loading the old "
                    "build; the window transport's native fast path is "
                    "disabled (Python fallback).  Run `make -C "
                    "bluefog_tpu/native` to refresh.", ", ".join(stale))
        try:
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return lib() is not None


def is_stale() -> bool:
    """True when the loaded library is older than its sources and could
    not be rebuilt (fast paths must not trust its symbols/layouts)."""
    lib()
    return _stale


def has_win_native() -> bool:
    """True when the loaded library carries the window-transport native
    hot path (``bf_wintx_*`` / ``bf_winsvc_drain``) — including the
    multi-stream stripe surface (``bf_wintx_stripe_stats``, whose absence
    marks a pre-stripe build with the OLD ``bf_wintx_start``/``send``
    signatures), the tracing surface (``bf_rec_snapshot``, whose absence
    marks a pre-trace build with the OLD ``bf_win_item_t`` layout) and
    the async step clock (``bf_trace_set_step``, whose absence marks a
    build with the 24-byte trace trailer and no ``trace_step`` item
    field) — and is not stale."""
    handle = lib()
    return (handle is not None and not _stale
            and hasattr(handle, "bf_wintx_start")
            and hasattr(handle, "bf_winsvc_drain")
            and hasattr(handle, "bf_wintx_stripe_stats")
            and hasattr(handle, "bf_rec_snapshot")
            and hasattr(handle, "bf_trace_set_step"))


def has_win_xla() -> bool:
    """True when the loaded library carries the zero-copy XLA put plans
    (``bf_xla_plan_*``, xlacall.cc) and is not stale.  The in-program
    ``bf_xla_win_put`` FFI handler is a further capability on top —
    :func:`has_xla_handler` — absent when the jaxlib FFI headers were
    missing at build time."""
    handle = lib()
    return (handle is not None and not _stale
            and hasattr(handle, "bf_xla_plan_new")
            and hasattr(handle, "bf_xla_plan_run"))


def has_xla_handler() -> bool:
    """True when the build also carries the ``bf_xla_win_put`` XLA FFI
    custom-call handler (compiled against the jaxlib FFI headers)."""
    handle = lib()
    return (has_win_xla() and hasattr(handle, "bf_xla_has_handler")
            and bool(handle.bf_xla_has_handler()))


_FASTCALL_ABI = 2
_fastcall = None
_fastcall_tried = False


def fastcall():
    """The optional ``_bf_fastcall`` METH_FASTCALL module (hot-path send
    binding), or None — missing module, stale core, or an ABI-version
    mismatch all fall back to the ctypes bindings, never misparse."""
    global _fastcall, _fastcall_tried
    if _fastcall_tried:
        return _fastcall
    _fastcall_tried = True
    if not has_win_native():
        return None
    try:
        from bluefog_tpu.native import _bf_fastcall  # type: ignore
    except ImportError:
        return None
    if getattr(_bf_fastcall, "ABI_VERSION", None) != _FASTCALL_ABI:
        import logging
        logging.getLogger("bluefog_tpu").warning(
            "_bf_fastcall ABI %s != expected %s (stale build?) — using the "
            "ctypes bindings", getattr(_bf_fastcall, "ABI_VERSION", None),
            _FASTCALL_ABI)
        return None
    _fastcall = _bf_fastcall
    return _fastcall
