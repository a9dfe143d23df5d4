"""jax.profiler capture hook.

``profile_capture(profile_dir)`` wraps a code region in a JAX profiler trace
when ``profile_dir`` is truthy and is a transparent no-op otherwise — so the
launchers and benchmark runner can take ``--profile-dir`` unconditionally.
The capture lands in ``<profile_dir>/plugins/profile/<ts>/`` ready for
TensorBoard's profile plugin; the serve step's ``jax.named_scope`` blocks
(probing / dispatch / scan / merge, and telemetry for the dedup counter)
make the op_profile tab read in LIRA's stage vocabulary instead of raw HLO op
names, and an enabled ``Tracer``'s spans land on the host plane beside them.
See README "Observability" for the capture → TensorBoard recipe.
"""
from __future__ import annotations

import contextlib
from typing import Optional

__all__ = ["profile_capture"]


@contextlib.contextmanager
def profile_capture(profile_dir: Optional[str]):
    """Capture a jax.profiler trace into ``profile_dir`` for the duration of
    the block; no-op when ``profile_dir`` is empty/None."""
    if not profile_dir:
        yield None
        return
    import jax

    jax.profiler.start_trace(str(profile_dir))
    try:
        yield str(profile_dir)
    finally:
        jax.profiler.stop_trace()
