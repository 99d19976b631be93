"""Layer 3: count XLA compilations during a scheduler session.

The PR 7 serving claim is that one warmup compile covers every
dispatch: ring restaging, pagestore residency swaps and fault plans all
reuse the single warmed ``engine_run_chunk_admit`` executable, so the
host never pays a compile on the critical path.  ``CompileGuard`` turns
that claim into a machine check by hooking jax's cache-miss path
(``jax._src.compiler.backend_compile_and_load``) and recording the name
of every HLO module that actually reaches the backend compiler.

Cache *hits* never reach this hook, so a guarded region that triggers
no compiles records nothing -- which is exactly the property we want to
assert.  That includes hits in JAX's persistent compilation cache: a
process that turned it on (``repro.launch.compile_cache``) counts only
modules the cache did not already hold.  Names are per-module symbols
like ``jit_engine_run_chunk_admit``, so callers filter with
``count("engine_run_chunk_admit")`` and are not confused by unrelated
tiny compiles (``jit_convert_element_type`` ...) or by the pagestore's
pow2-padded ``_scatter_frames`` variants.

Usage::

    with CompileGuard() as cg:
        ids, dists, stats = stream_search(...)
    assert cg.count("engine_run_chunk_admit") == 1

or enforcing inline::

    with CompileGuard(match="engine_run_chunk", max_compiles=1):
        ...
"""
from __future__ import annotations

from typing import Optional


def _module_name(module) -> str:
    """Best-effort symbol name of the MLIR module being compiled."""
    try:
        return str(module.operation.attributes["sym_name"]).strip('"')
    except Exception:
        try:
            return str(getattr(module, "name", "")) or "<unknown>"
        except Exception:  # pragma: no cover
            return "<unknown>"


class CompileGuard:
    """Context manager recording every backend compilation by name.

    Parameters
    ----------
    match:
        Optional substring; when given together with ``max_compiles``,
        only matching module names count against the limit.
    max_compiles:
        When set, exiting the context raises ``RuntimeError`` if more
        than this many (matching) compilations were observed.  The check
        is skipped when the body is already raising, so it never masks
        the original error.
    """

    def __init__(self, match: Optional[str] = None,
                 max_compiles: Optional[int] = None):
        self.match = match
        self.max_compiles = max_compiles
        self.names: list = []
        self._orig = None

    # -- queries -----------------------------------------------------------
    def count(self, substring: Optional[str] = None) -> int:
        """Number of recorded compilations whose name contains substring."""
        if substring is None:
            return len(self.names)
        return sum(1 for n in self.names if substring in n)

    @property
    def total(self) -> int:
        return len(self.names)

    # -- context protocol --------------------------------------------------
    def __enter__(self):
        from jax._src import compiler
        self._orig = orig = compiler.backend_compile_and_load
        names = self.names

        def _recording_compile(backend, module, *args, **kwargs):
            names.append(_module_name(module))
            return orig(backend, module, *args, **kwargs)

        compiler.backend_compile_and_load = _recording_compile
        return self

    def __exit__(self, exc_type, exc, tb):
        from jax._src import compiler
        compiler.backend_compile_and_load = self._orig
        if exc_type is None and self.max_compiles is not None:
            n = self.count(self.match)
            if n > self.max_compiles:
                matching = [x for x in self.names
                            if self.match is None or self.match in x]
                raise RuntimeError(
                    f"CompileGuard: {n} compilation(s) observed "
                    f"(limit {self.max_compiles}"
                    + (f", match={self.match!r}" if self.match else "")
                    + f"): {matching}")
        return False
