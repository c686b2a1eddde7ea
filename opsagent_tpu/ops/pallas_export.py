"""A Pallas kernel traced and lowered once a shape.

Tracing a kernel's body and lowering it to Mosaic is Python work that
every step program holding the kernel would repeat: 1.7-3 s of tracing a
distinct shape and 0.2 s of lowering a program on the chip's host, 48 s of
a 68 s warm-up at the 72B cell's 8 shapes and some 50 programs, with every
executable already in the compile cache (PERF.md section 6, PR 29). So
each shape is traced and lowered ONCE, exported (``jax.export``: the
lowered module as bytes), and every program after that inlines the bytes;
beside JAX's compile cache, where one is set, the bytes also outlive the
process, keyed by the kernel file's own text, JAX's version and the shape.
"""

from __future__ import annotations

import hashlib
import os
import threading

import jax


def export_path(name: str, source: str, shape: dict) -> str | None:
    """Where a shape's exported kernel lives: in JAX's persistent compile
    cache directory, if the process has one."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return None
    with open(source, "rb") as f:
        text = f.read()
    key = hashlib.sha256(
        text + repr((jax.__version__, sorted(shape.items()))).encode()
    ).hexdigest()[:32]
    return os.path.join(cache_dir, f"{name}-{key}.export")


def exported_call(call, args, *, name: str, source: str, shape: dict,
                  scope: str):
    """``call`` (a ``pallas_call`` of one shape, taking ``args``' shapes) as
    a function that inlines its exported bytes: read back from beside the
    compile cache where they are, else traced under ``scope`` (the name the
    traces' readers know the kernel's time by), exported for the TPU and
    written there."""
    path = export_path(name, source, shape)
    if path and os.path.exists(path):
        try:
            with open(path, "rb") as f:
                return jax.export.deserialize(bytearray(f.read())).call
        except Exception:  # noqa: BLE001 - a torn file is a cold start
            pass

    def scoped(*a):
        with jax.named_scope(scope):
            return call(*a)

    exported = jax.export.export(jax.jit(scoped), platforms=("tpu",))(*args)
    if path:
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(exported.serialize())
            os.replace(tmp, path)
        except OSError:
            pass
    return exported.call
