"""Build-on-demand C++ shared libraries, keyed by their source.

The library a process loads is a function of the tracked ``.cc`` beside
it: the file name carries a hash of the source, so a copied tree, a
stale build or an edited source can never load a library that was not
built from the text on disk. The compiler writes to a temporary name
and the result moves into place with ``os.replace`` — edge processes
and cluster children may be loading the same path concurrently.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Optional, Tuple


def library_path(src: str) -> str:
    """``<dir>/_<stem>.<source-hash>.so`` for the source file `src`."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    stem = os.path.splitext(os.path.basename(src))[0]
    return os.path.join(os.path.dirname(src), f"_{stem}.{digest}.so")


def build_library(src: str) -> Tuple[Optional[str], str]:
    """(path of the library built from `src`, "") — compiling it with
    g++ when no build of this exact source exists yet — or (None, why)
    when the source or the compiler is unavailable or the build fails."""
    try:
        so = library_path(src)
    except OSError as e:
        return None, f"{src}: {e}"
    if os.path.exists(so):
        return so, ""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        tail = e.stderr.decode("utf-8", errors="replace").strip()[-400:]
        return None, f"g++ failed on {src}: {tail}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return None, f"g++ unavailable for {src}: {e}"
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so, ""
