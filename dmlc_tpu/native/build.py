"""Build the native engine: ``python -m dmlc_tpu.native.build``.

Compiles native/src/engine.cc into libdmlc_tpu.so next to this file
(g++ -O3 -march=native; zlib when the host has it — the Parquet GZIP
page codec — no other external deps). The reference's CMake/Makefile
build glue (CMakeLists.txt, make/dmlc.mk) maps to this single-step
build plus pyproject.toml for the Python side.

The ``.so`` is never committed: :func:`ensure_built` (called by
``native_available()`` on first use) builds it from the committed
source whenever it is missing or its stamp — a hash of the source, the
compile command (zlib or not included) and this host's CPU
(``-march=native``) — does not match. The stamp lives next to the
``.so`` (``libdmlc_tpu.so.stamp``). Concurrent callers (pytest-xdist
workers) serialize on a file lock; each build goes to a temp file that
is renamed over the ``.so`` only after it passed the ABI probe, so no
process ever maps a half-written library.

The build ASSERTS the compiled engine's ABI (``dtp_version()``, 8
since the columnar-page + image-payload decode) equals
``bindings.ABI_VERSION`` in a subprocess probe — a stale source tree
fails the BUILD loudly instead of at first use.
"""

from __future__ import annotations

import fcntl
import hashlib
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src", "engine.cc")
OUT = os.path.join(HERE, "libdmlc_tpu.so")


_ZLIB_FLAGS = None


def zlib_flags() -> list:
    """``["-lz"]`` when the toolchain can compile AND link against
    zlib (the engine's Parquet GZIP page decode), else
    ``["-DDTP_NO_ZLIB"]`` — the engine builds either way; without
    zlib, GZIP-coded pages raise EngineError naming the rebuild.
    Decided by a trial compile+link (not a header-path guess: SDK/
    sysroot layouts put zlib.h where only the compiler can see it,
    and engine.cc's own ``__has_include`` probe must agree with the
    link line or the build breaks one way or the other). Shared with
    the test-binary builds (tests/test_native.py) so every target
    links the same way; cached per process."""
    global _ZLIB_FLAGS
    if _ZLIB_FLAGS is not None:
        return list(_ZLIB_FLAGS)
    import tempfile
    with tempfile.TemporaryDirectory(prefix="dtp_zlib_probe_") as d:
        src = os.path.join(d, "probe.cc")
        with open(src, "w") as f:
            f.write("#include <zlib.h>\n"
                    "int main() { return zlibVersion() == nullptr; }\n")
        try:
            ok = subprocess.run(
                ["g++", "-std=c++17", src, "-o",
                 os.path.join(d, "probe"), "-lz"],
                capture_output=True, timeout=60).returncode == 0
        except (OSError, subprocess.SubprocessError):
            ok = False
    _ZLIB_FLAGS = ["-lz"] if ok else ["-DDTP_NO_ZLIB"]
    return list(_ZLIB_FLAGS)


def _flags() -> list:
    return ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
            "-pthread", "-Wall", "-Wextra"]


def _cpu_signature() -> str:
    """What ``-march=native`` compiles for: the host's CPU flags. A
    library built on another machine (a copied checkout) must not be
    loaded here — it may use instructions this CPU lacks."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.strip()
    except OSError:
        pass
    import platform
    return platform.machine() + platform.processor()


def stamp_for(src: str = SRC) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_flags() + zlib_flags()).encode())
    h.update(_cpu_signature().encode())
    return h.hexdigest()


def _stamp_path(out: str) -> str:
    return out + ".stamp"


def is_current(src: str = SRC, out: str = OUT) -> bool:
    """True when ``out`` exists and was built from ``src`` as it is
    now, with today's flags, on this CPU."""
    try:
        with open(_stamp_path(out)) as f:
            recorded = f.read().strip()
    except OSError:
        return False
    return os.path.exists(out) and recorded == stamp_for(src)


def build(verbose: bool = True, src: str = SRC, out: str = OUT) -> str:
    """Compile ``src`` into ``out`` unconditionally (under the lock)."""
    with _locked(out):
        return _build_locked(verbose, src, out)


def ensure_built(src: str = SRC, out: str = OUT) -> str:
    """Build ``out`` from ``src`` unless the stamp says it is current.
    Safe to call from many processes at once: one builds, the others
    wait on the lock and then find the stamp current."""
    if is_current(src, out):
        return out
    with _locked(out):
        if not is_current(src, out):
            _build_locked(False, src, out)
    return out


class _locked:
    def __init__(self, out: str):
        self._path = out + ".lock"

    def __enter__(self):
        self._fd = os.open(self._path, os.O_RDWR | os.O_CREAT, 0o644)
        fcntl.flock(self._fd, fcntl.LOCK_EX)
        return self

    def __exit__(self, *exc):
        fcntl.flock(self._fd, fcntl.LOCK_UN)
        os.close(self._fd)


def _build_locked(verbose: bool, src: str, out: str) -> str:
    stamp = stamp_for(src)
    tmp = f"{out}.tmp{os.getpid()}"
    cmd = ["g++"] + _flags() + [src, "-o", tmp] + zlib_flags()
    if verbose:
        print("+", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=not verbose)
        _check_abi(tmp)
        # the .so first, then its stamp: a reader that sees the new
        # stamp always finds the new library behind it
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(_stamp_path(out) + ".tmp", "w") as f:
        f.write(stamp + "\n")
    os.replace(_stamp_path(out) + ".tmp", _stamp_path(out))
    return out


def _check_abi(path: str) -> None:
    """Fail the build — loudly, at build time — when the freshly
    compiled engine does not speak the ABI the bindings expect.

    The probe runs in a SUBPROCESS: dlopen in this process would
    resolve the path to an already-mapped old copy (a REPL that used
    bindings before rebuilding) and fail a perfectly good rebuild."""
    from dmlc_tpu.native.bindings import ABI_VERSION
    out = subprocess.run(
        [sys.executable, "-c",
         "import ctypes, sys; lib = ctypes.CDLL(sys.argv[1]); "
         "lib.dtp_version.restype = ctypes.c_int; "
         "print(lib.dtp_version())", path],
        capture_output=True, text=True, timeout=60)
    if out.returncode != 0:
        raise RuntimeError(
            f"built {path} failed the ABI probe: {out.stderr.strip()}")
    got = int(out.stdout.strip())
    if got != ABI_VERSION:
        raise RuntimeError(
            f"built {path} speaks ABI {got}, bindings expect "
            f"{ABI_VERSION} — src/engine.cc and bindings.py are out of "
            "sync (bump dtp_version()/ABI_VERSION together)")


if __name__ == "__main__":
    path = build()
    print(f"built {path}")
    sys.exit(0)
