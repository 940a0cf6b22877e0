"""Shared build-from-source loader for the native/ C++ modules.

The ``.so`` files are never trusted as found: a checkout may be a copy
whose ``native/build/`` is stale and whose mtimes mean nothing.  The
first load of a module in a PROCESS TREE compiles it from
``native/<src>`` unconditionally (the ``M3_NATIVE_BUILT`` environment
variable, inherited by children, records what this tree has built, so
a launcher's node processes reuse their parent's build); a failed build
raises :class:`NativeBuildError` — never a silent ``None`` that would
put a node on the slow Python path unnoticed.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent.parent
_BUILT_ENV = "M3_NATIVE_BUILT"
_cache: dict[str, object] = {}


class NativeBuildError(RuntimeError):
    """native/<src> did not compile or load."""


def load_native(src_name: str, so_name: str, extra_flags: tuple = ()):
    """CDLL for native/<src_name>, built into native/build/<so_name>.
    Results are cached per so_name."""
    if so_name in _cache:
        return _cache[so_name]
    src = _ROOT / "native" / src_name
    so = _ROOT / "native" / "build" / so_name
    built = os.environ.get(_BUILT_ENV, "").split(":")
    if so_name not in built or not so.exists():
        so.parent.mkdir(parents=True, exist_ok=True)
        # Compile to a unique temp path and rename into place: several
        # processes sharing the checkout may build concurrently, and
        # dlopen of a half-written .so must not happen.
        tmp = so.with_suffix(f".tmp{os.getpid()}")
        try:
            subprocess.run(
                ["g++", "-O2", *extra_flags, "-shared", "-fPIC",
                 "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=300,
            )
            os.replace(tmp, so)
        except (subprocess.SubprocessError, OSError) as e:
            tmp.unlink(missing_ok=True)
            detail = getattr(e, "stderr", b"") or b""
            raise NativeBuildError(
                f"building native/{src_name} failed: {e}\n"
                f"{detail.decode(errors='replace')[-2000:]}") from e
        os.environ[_BUILT_ENV] = ":".join(b for b in built + [so_name] if b)
    try:
        lib = ctypes.CDLL(str(so))
    except OSError as e:
        raise NativeBuildError(f"loading {so} failed: {e}") from e
    _cache[so_name] = lib
    return lib
