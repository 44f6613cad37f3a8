"""Runtime sanitizer rail: `jax.experimental.checkify` threading (DESIGN §9.2).

The repo's bug history is silent trace-level corruption — NaN through a lossy
codec, a singular SMW pivot dividing to inf, a clamped padding index walking
off the trial batch.  This module is the ONE switchboard for turning those
into *located* runtime errors:

    with sanitize_scope("raise"):        # trace-time flag
        err, out = checkify.checkify(fn)(*args)
    err.throw()                          # names the failing site

Check sites live in the hot paths (`covstate._smw_pieces`, the transport
relay, the sweep bodies, the batch trial padding) but are guarded by
`checks_enabled()` — a *trace-time* Python flag, so when checks are off the
traced program contains literally zero extra operations and compiled
histories stay bit-for-bit identical to an unsanitized build (tested).

The flag rides the jit cache correctly because every enabling path also keys
the compiled program on the knob: `ICOAConfig.checks` is part of the static
`cfg` argument of `icoa.sweep`, and `BackendSpec.checks` is part of the spec
the batch programs close over.  `checked(fn)` is the entry-point wrapper:
it holds the scope open across the trace (so the sites insert) and throws
the functionalized error after the run.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Iterator, Tuple, TypeVar

import jax.numpy as jnp
from jax._src.interpreters import batching
from jax._src.lax import lax as _lax_internal
from jax.experimental import checkify

# checkify keeps a while-loop's cond checks alive with a `dce_sink` in the
# body.  jax 0.9's vmap rule for that primitive hands its operand back as an
# output although it has none, so vmap-of-checkify-of-while (the batch trial
# rail, api.runner) fails with "foreach() argument 2 is longer than argument
# 1".  The primitive has no outputs, batched or not.
batching.primitive_batchers[_lax_internal.dce_sink_p] = (
    lambda args, dims, **params: ([], []))

__all__ = ["CHECK_MODES", "checks_enabled", "sanitize_scope", "checked",
           "check_finite", "check_nonzero", "check_in_bounds",
           "validate_mode"]

CHECK_MODES: Tuple[str, ...] = ("off", "raise")

_F = TypeVar("_F", bound=Callable[..., Any])

_state = threading.local()


def validate_mode(mode: str, where: str = "checks") -> str:
    if mode not in CHECK_MODES:
        raise ValueError(f"unknown {where} mode {mode!r}; "
                         f"pick one of {CHECK_MODES}")
    return mode


def checks_enabled() -> bool:
    """True while tracing under an enabled `sanitize_scope` — the guard every
    check site consults before inserting a `checkify.check`."""
    return bool(getattr(_state, "enabled", False))


@contextlib.contextmanager
def sanitize_scope(mode: str) -> Iterator[None]:
    """Set the trace-time check flag for the dynamic extent of the scope.

    The innermost scope wins: `icoa.sweep` re-asserts its own `cfg.checks`
    so the static jit key stays authoritative for what its cached program
    contains, regardless of the ambient flag at call time.
    """
    validate_mode(mode)
    prev = checks_enabled()
    _state.enabled = mode == "raise"
    try:
        yield
    finally:
        _state.enabled = prev


def checked(fn: _F) -> Callable[..., Any]:
    """Wrap `fn` so the repo's check sites insert AND failures raise.

    The returned callable traces `fn` under `checkify.checkify` with the
    sanitize scope held open (user checks only: the sites below give better
    messages than blanket float checks), then throws the accumulated error —
    a `checkify.JaxRuntimeError` naming the failing site.
    """
    cfn = checkify.checkify(fn)

    @functools.wraps(fn)
    def run(*args: Any, **kwargs: Any) -> Any:
        with sanitize_scope("raise"):
            err, out = cfn(*args, **kwargs)
        checkify.check_error(err)
        return out

    return run


# ------------------------------------------------------------- check sites
# Each helper is a no-op passthrough unless tracing under an enabled scope;
# when enabled it inserts one functionalized check naming `site`.


def check_finite(x: jnp.ndarray, site: str) -> jnp.ndarray:
    """Assert every element of `x` is finite (no NaN/Inf)."""
    if checks_enabled():
        checkify.check(jnp.all(jnp.isfinite(x)),
                       f"non-finite value in {site}")
    return x


def check_nonzero(x: jnp.ndarray, site: str) -> jnp.ndarray:
    """Assert `x` (a divisor) is nowhere exactly zero."""
    if checks_enabled():
        checkify.check(jnp.all(x != 0), f"division by zero in {site}")
    return x


def check_in_bounds(idx: jnp.ndarray, size: int, site: str) -> jnp.ndarray:
    """Assert every index in `idx` lies in [0, size)."""
    if checks_enabled():
        checkify.check(jnp.all((idx >= 0) & (idx < size)),
                       f"index out of bounds [0, {size}) in {site}")
    return idx
