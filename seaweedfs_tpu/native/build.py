"""Build the native libraries: g++ -> libseaweed_native.so (codec) and
libseaweed_dataplane.so (HTTP data plane).

Run directly (`python seaweedfs_tpu/native/build.py`) or let
seaweedfs_tpu.native build lazily on first import. No pybind11 — the
ABI is a C `extern "C"` surface consumed via ctypes.

A built library is used only when it was built here from exactly the
committed source with exactly these flags: each .so has a sidecar
``.key`` holding a hash of source + command line + this CPU, and any
mismatch (a new source, other flags, a .so copied in from another
machine) rebuilds it. ``-march=native`` makes the CPU part of the key.

Sanitizer builds: ``SEAWEEDFS_TPU_DP_SANITIZE={asan,tsan}`` selects an
instrumented data-plane build. Each mode caches its own .so
(libseaweed_dataplane.asan.so / .tsan.so) so switching modes never
races the plain library, and instrumented builds drop -O3/-march for
-O1 -g -fno-omit-frame-pointer so reports carry usable stacks.
"""
from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "gf256_codec.cc")
LIB = os.path.join(HERE, "libseaweed_native.so")
DP_SRC = os.path.join(HERE, "dataplane.cc")
DP_LIB = os.path.join(HERE, "libseaweed_dataplane.so")

SANITIZE_ENV = "SEAWEEDFS_TPU_DP_SANITIZE"
SANITIZE_FLAGS = {
    "asan": ["-fsanitize=address"],
    "tsan": ["-fsanitize=thread"],
}


def sanitize_mode() -> str:
    """'' (plain), 'asan', or 'tsan' — from the environment."""
    mode = os.environ.get(SANITIZE_ENV, "").strip().lower()
    if mode in ("", "0", "off", "none"):
        return ""
    if mode not in SANITIZE_FLAGS:
        raise ValueError(
            f"{SANITIZE_ENV}={mode!r}: expected one of "
            f"{sorted(SANITIZE_FLAGS)} (or empty)")
    return mode


def dp_lib_path(mode: str | None = None) -> str:
    mode = sanitize_mode() if mode is None else mode
    if not mode:
        return DP_LIB
    base, ext = os.path.splitext(DP_LIB)
    return f"{base}.{mode}{ext}"


def _cpu_id() -> str:
    """This kind of CPU: the architecture plus the feature flags that
    -march=native compiles for."""
    flags = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()} {flags}"


def build_key(src: str, flags: list[str]) -> str:
    """Hash of what a built library depends on: the source bytes, the
    compiler flags and this CPU."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(flags).encode())
    h.update(_cpu_id().encode())
    return h.hexdigest()


def _read_key(lib: str) -> str:
    try:
        with open(lib + ".key", encoding="utf-8") as f:
            return f.read().strip()
    except OSError:
        return ""


def _compile(src: str, lib: str, verbose: bool,
             extra: list[str] | None = None,
             opt: list[str] | None = None) -> str:
    flags = (opt or ["-O3", "-march=native"]) + \
        ["-shared", "-fPIC", "-std=c++17"] + (extra or [])
    key = build_key(src, flags)
    if os.path.exists(lib) and _read_key(lib) == key:
        return lib
    # compile to a temp name + rename so a concurrent process never
    # dlopens a half-written library; the key lands after the library,
    # so a reader that sees a new key also sees the new library
    tmp = lib + f".tmp{os.getpid()}"
    cmd = ["g++"] + flags + ["-o", tmp, src]
    if verbose:
        print("+", " ".join(cmd), file=sys.stderr)
    subprocess.run(cmd, check=True, capture_output=not verbose)
    os.replace(tmp, lib)
    with open(tmp + ".key", "w", encoding="utf-8") as f:
        f.write(key)
    os.replace(tmp + ".key", lib + ".key")
    return lib


def build(verbose: bool = True) -> str:
    """Compile the codec library if missing or stale; returns its path."""
    return _compile(SRC, LIB, verbose)


def build_dataplane(verbose: bool = True,
                    mode: str | None = None) -> str:
    """Compile the data-plane library; returns its path. `mode` (or
    the SEAWEEDFS_TPU_DP_SANITIZE env var) selects an instrumented
    build cached under its own name."""
    mode = sanitize_mode() if mode is None else mode
    if not mode:
        return _compile(DP_SRC, DP_LIB, verbose, extra=["-pthread"])
    return _compile(DP_SRC, dp_lib_path(mode), verbose,
                    extra=["-pthread"] + SANITIZE_FLAGS[mode],
                    opt=["-O1", "-g", "-fno-omit-frame-pointer"])


if __name__ == "__main__":
    build()
    print(LIB)
    build_dataplane()
    print(dp_lib_path())
