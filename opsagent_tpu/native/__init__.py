"""Native (C++) runtime components, loaded via ctypes.

The compute path of this framework is JAX/XLA/Pallas; the host runtime
around it uses native code where Python would sit on a hot or
latency-sensitive path. First component: the constrained-decoding FSM
matcher (fsm_matcher.cc) — eagerly precomputed [states x vocab] token
admissibility + destination tables with O(row-copy) per-step cost.

Build story: no pybind11 in this image, so the module is a flat C ABI
compiled on first use with g++ into ``_native-<digest>.so`` next to the
sources, the digest being that of ``fsm_matcher.cc`` as it stands: a
library built from other source text has another name and is never
loaded, whatever its mtime. If g++ or the build is unavailable, callers
use the pure-Python implementations (`OPSAGENT_NATIVE=0` forces that);
``impl()`` says which one serves, and ``/healthz`` reports it.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

from ..utils.logger import get_logger

log = get_logger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fsm_matcher.cc")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_native-{digest}.so")


def _build(so: str) -> bool:
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
        "-o", tmp, _SRC,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build unavailable (%s); using Python fallback", e)
        return False
    if proc.returncode != 0:
        log.warning(
            "native build failed; using Python fallback: %s",
            proc.stderr[-500:],
        )
        return False
    os.replace(tmp, so)  # atomic: a concurrent loader never sees half a file
    for stale in glob.glob(os.path.join(_DIR, "_native*.so")):
        if stale != so:
            try:
                os.remove(stale)
            except OSError:
                pass
    return True


def build_native() -> None:
    """Ahead-of-time build entry point (Docker image build / CI): compile
    the library now and fail loudly, instead of the lazy build-on-first-
    use with graceful fallback that ``get_lib`` does at runtime."""
    if not _build(_so_path()):
        raise RuntimeError("native build failed (see log for compiler output)")


def impl() -> str:
    """Which FSM-table implementation serves constrained requests in this
    process: "native" (the C++ library, built from the source as it
    stands) or "python" (the lazy numpy path)."""
    return "native" if get_lib() is not None else "python"


def get_lib():
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _build_failed
    if os.environ.get("OPSAGENT_NATIVE", "1") == "0":
        return None
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("native load failed (%s); using Python fallback", e)
            _build_failed = True
            return None
        lib.opsagent_fsm_build.restype = ctypes.c_void_p
        lib.opsagent_fsm_build.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32,
        ]
        lib.opsagent_fsm_num_states.restype = ctypes.c_int32
        lib.opsagent_fsm_num_states.argtypes = [ctypes.c_void_p]
        lib.opsagent_fsm_mask.restype = None
        lib.opsagent_fsm_mask.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p
        ]
        lib.opsagent_fsm_advance.restype = ctypes.c_int32
        lib.opsagent_fsm_advance.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32
        ]
        lib.opsagent_fsm_free.restype = None
        lib.opsagent_fsm_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        log.info("native runtime loaded (%s)", so)
        return _lib


class NativeFSMTables:
    """Precomputed token-admissibility tables over a byte DFA (C++ owned).

    Mirrors the lazy Python TokenFSM interface: ``mask_for_state`` and
    ``advance``. Construction runs the full [states x vocab] precompute in
    parallel native threads."""

    def __init__(
        self,
        dfa_next: np.ndarray,     # [num_states*256] int32
        dfa_accept: np.ndarray,   # [num_states] bool
        token_bytes: list[bytes],
        eos_id: int,
    ):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self.vocab = len(token_bytes)
        self.num_states = len(dfa_accept)
        self._next = np.ascontiguousarray(dfa_next, np.int32)
        self._accept = np.ascontiguousarray(
            np.asarray(dfa_accept), np.uint8
        )
        blob = b"".join(token_bytes)
        offsets = np.zeros((self.vocab + 1,), np.int32)
        offsets[1:] = np.cumsum([len(tb) for tb in token_bytes])
        self._blob = np.frombuffer(blob, np.uint8) if blob else np.zeros(
            (1,), np.uint8
        )
        self._offsets = np.ascontiguousarray(offsets)
        self._handle = lib.opsagent_fsm_build(
            self._next.ctypes.data, self._accept.ctypes.data,
            ctypes.c_int32(self.num_states),
            self._blob.ctypes.data, self._offsets.ctypes.data,
            ctypes.c_int32(self.vocab), ctypes.c_int32(eos_id),
            ctypes.c_int32(0),
        )
        if not self._handle:
            raise RuntimeError("native FSM build returned null")

    def mask_for_state(self, state: int) -> np.ndarray:
        out = np.empty((self.vocab,), np.uint8)
        self._lib.opsagent_fsm_mask(
            self._handle, ctypes.c_int32(state), out.ctypes.data
        )
        return out.astype(bool)

    def advance(self, state: int, token_id: int) -> int:
        return int(
            self._lib.opsagent_fsm_advance(
                self._handle, ctypes.c_int32(state), ctypes.c_int32(token_id)
            )
        )

    def __del__(self):
        lib, handle = getattr(self, "_lib", None), getattr(self, "_handle", None)
        if lib is not None and handle:
            lib.opsagent_fsm_free(handle)
            self._handle = None
