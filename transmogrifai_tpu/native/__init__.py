"""Native runtime components (C++), built lazily with the system toolchain.

The reference's ingestion/runtime layer is JVM code running on Spark
executors; this framework's equivalent native layer lives here.  Modules are
compiled on first use with ``g++`` (no pip/network) into a git-ignored
``_build/`` next to the package, and every consumer has a pure-Python
fallback — absence of a toolchain degrades performance, never correctness.

A binary is only ever reused when it was built from exactly the source now
on disk: its file name carries a digest of the ``.cpp`` bytes and of the
interpreter/numpy ABI it was compiled against, so a copied tree cannot run a
``.so`` that git never saw the source of.  A module that could not be built
or imported says why through :func:`fallback_reasons` (and a warning), so a
caller that requires the native path — ``chip_smoke.py`` — can fail on it.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading
import warnings
from typing import Any, Dict, Optional

MODULES = ("datewire", "fastcsv", "fasttok", "locofmt", "mapprof", "numdist",
           "textprof")

_CACHE: dict = {}
_REASONS: Dict[str, str] = {}
# one build and import at a time: the first ``load`` of a module may come
# from several of a pool's workers at once, and a second compile into the
# same temporary file would lose its rename to the first
_LOCK = threading.Lock()


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
    os.makedirs(d, exist_ok=True)
    return d


def _source_path(name: str) -> str:
    # native/ sources live at the repo root next to the package
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg_root), "native", f"{name}.cpp")


def _compile(name: str) -> str:
    """Path of ``_<name>.so`` built from the current source; raises with the
    compiler's own words when it cannot be built."""
    import numpy as np
    src = _source_path(name)
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read())
    digest.update(f"{sys.version}|{np.__version__}".encode())
    so = os.path.join(_build_dir(), f"_{name}.{digest.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"), "-O2", "-std=c++17", "-shared", "-fPIC",
        f"-I{sysconfig.get_paths()['include']}",
        f"-I{np.get_include()}",
        src, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)     # concurrent builders converge on one file
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"{cmd[0]} failed (rc={e.returncode}): "
            f"{e.stderr.decode(errors='replace')[-800:]}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def load(name: str) -> Optional[Any]:
    """Import native module ``_<name>``, compiling it if needed.  Returns the
    module or None (callers fall back to pure Python, and
    :func:`fallback_reasons` says why).  Disable with TRANSMOGRIFAI_NATIVE=0."""
    if name in _CACHE:
        return _CACHE[name]
    with _LOCK:
        if name not in _CACHE:
            _CACHE[name] = _build_and_import(name)
    return _CACHE[name]


def _build_and_import(name: str) -> Optional[Any]:
    mod = None
    if os.environ.get("TRANSMOGRIFAI_NATIVE", "1") == "0":
        _REASONS[name] = "disabled by TRANSMOGRIFAI_NATIVE=0"
    else:
        try:
            spec = importlib.util.spec_from_file_location(
                f"_{name}", _compile(name))
            mod = importlib.util.module_from_spec(spec)
            sys.modules[f"_{name}"] = mod
            spec.loader.exec_module(mod)
        except Exception as e:  # noqa: BLE001 — toolchain-dependent; the
            # Python path is exact, so degrade — but never silently
            mod = None
            sys.modules.pop(f"_{name}", None)
            _REASONS[name] = f"{type(e).__name__}: {e}"
            warnings.warn(f"native module {name!r} unavailable, using the "
                          f"pure-Python path: {_REASONS[name]}",
                          RuntimeWarning, stacklevel=3)
    return mod


def fallback_reasons() -> Dict[str, str]:
    """Loads every module in :data:`MODULES`; returns ``{name: reason}`` for
    those running on the pure-Python path (empty when all are native)."""
    for name in MODULES:
        load(name)
    return {n: _REASONS[n] for n in MODULES if _CACHE.get(n) is None}
