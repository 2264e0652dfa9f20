"""Native (C++) components of ray_tpu.

Currently: the shared-memory object store (objstore.cc — the host tier of
the object plane, reference: src/ray/object_manager/plasma/) and the
zero-staging TCP transfer plane (xfer.cc — reference:
src/ray/object_manager/object_manager.cc push/pull). Compiled lazily on
first import so a fresh checkout needs no separate build step.
"""

import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
OBJSTORE_SO = os.path.join(_HERE, "libraytpu_objstore.so")


def ensure_built() -> str:
    """Compile the native library if missing or older than its sources.
    Other processes (test workers, every spawned worker) may be loading
    the library meanwhile, so the build goes to a sibling name and is
    renamed into place: a reader sees the old file or the new, never a
    half-written one."""
    srcs = [os.path.join(_HERE, "objstore.cc"),
            os.path.join(_HERE, "xfer.cc")]
    if (not os.path.exists(OBJSTORE_SO)
            or os.path.getmtime(OBJSTORE_SO) < max(
                os.path.getmtime(s) for s in srcs)):
        tmp = os.path.join(_HERE, f".libraytpu_objstore.{os.getpid()}.so")
        try:
            subprocess.run(
                ["make", "-C", _HERE, f"OUT={tmp}", "all"],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, OBJSTORE_SO)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    return OBJSTORE_SO
