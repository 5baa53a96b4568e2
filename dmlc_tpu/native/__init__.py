"""Native C++ engine loader.

The hot byte path (InputSplit sharding, text→CSR parse, prefetch) has a
C++ implementation (native/src/*.cc) built as a shared library and bound
via ctypes (no pybind11 in this environment). This module builds it from
the committed source on first use (``build.ensure_built``: missing, or
stamped for another source or CPU) and loads it lazily. When it cannot
be built or loaded, ``engine="auto"`` callers get the pure-Python golden
engines with identical semantics and one warning; ``engine="native"``
callers get the error.

Force a rebuild: ``python -m dmlc_tpu.native.build``.
"""

from __future__ import annotations

from typing import Optional

_lib = None
_tried = False
_load_error: Optional[str] = None


def native_available() -> bool:
    global _lib, _tried, _load_error
    if not _tried:
        _tried = True
        try:
            from dmlc_tpu.native import bindings, build
            _lib = bindings.load(build.ensure_built())
        except Exception as e:  # noqa: BLE001
            # an engine that cannot be built or loaded must not silently
            # degrade to the Python engines: say why once, and keep the
            # reason for get_lib()'s error
            _lib = None
            _load_error = f"{e}{_stderr_tail(e)}"
            # all_ranks: the .so is HOST-local — in an ssh gang one
            # host's failed build silently costs that rank ~10x while
            # rank 0's loads fine, so every rank must say it
            from dmlc_tpu.obs.log import warn_once
            warn_once("native-engine-unusable",
                      f"native engine unusable ({_load_error}); "
                      "using Python engines", all_ranks=True)
    return _lib is not None


def _stderr_tail(e: Exception) -> str:
    err = getattr(e, "stderr", None)
    if isinstance(err, bytes):
        err = err.decode(errors="replace")
    return f": {err.strip()[-500:]}" if err else ""


def get_lib():
    if not native_available():
        from dmlc_tpu.utils.logging import DMLCError
        detail = (f" (load failed: {_load_error})" if _load_error
                  else "")
        raise DMLCError(f"native engine unavailable{detail}")
    return _lib


def __getattr__(name: str):
    # NativeLibSVMParser / NativeCSVParser live in bindings; resolve lazily
    if name in ("NativeLibSVMParser", "NativeCSVParser",
                "NativeLibFMParser"):
        from dmlc_tpu.native import bindings
        return getattr(bindings, name)
    raise AttributeError(name)
