"""Central kernel-dispatch registry.

Every compute hot-spot in ``repro.kernels`` has several interchangeable
implementations (fused Pallas kernel, blocked XLA path, jnp reference,
sequential oracle). This module owns the choice between them so the five
kernel packages share one selection policy instead of five copy-pasted
``_on_tpu()`` if-chains.

Variants register with :func:`kernel_variant`:

    @kernel_variant("mamba2_ssd", "pallas", priority=100,
                    predicate=lambda ctx: ctx["S"] % ctx["chunk"] == 0,
                    auto_predicate=lambda ctx: ctx["on_tpu"])
    def _pallas(...): ...

* ``predicate`` is a hard capability check (shape constraints, argument
  restrictions). A variant whose predicate rejects the call context is never
  used — an explicit request for it silently falls back to the best capable
  variant, matching the legacy ops behaviour (e.g. ``impl='pallas'`` with a
  non-divisible sequence length runs the jnp path).
* ``auto_predicate`` is a soft preference consulted only under
  ``impl='auto'`` (e.g. prefer Pallas on TPU, prefer the blocked XLA path for
  long sequences on CPU). Explicit requests bypass it.
* ``priority`` orders candidates; highest capable+preferred wins under
  ``auto``, highest capable wins as the fallback.

Selection can be overridden without touching call sites: :func:`force_impl`
is a context manager (``with force_impl("jnp"): ...``), optionally scoped to
one kernel, that outranks the call-site ``impl=`` argument (default
``"auto"``). Innermost wins. Thread-local, and resolved at *trace* time for
jitted code.

Introspection for benchmarks and tests: :func:`available_impls`,
:func:`KernelRegistry.kernels`, :func:`KernelRegistry.get`, and
:func:`KernelRegistry.record`, which collects the variants actually chosen
inside a ``with`` block (how a run on the chip proves that no kernel fell
back from Pallas).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional

Ctx = Mapping[str, Any]
Predicate = Callable[[Ctx], bool]


def on_tpu() -> bool:
    """True when the default jax backend is a TPU (shared by all kernels).
    A backend that fails to initialise raises here rather than reading as
    "not a TPU": that would quietly run the Pallas interpreter on the chip."""
    import jax

    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class Variant:
    """One registered implementation of a kernel."""

    kernel: str
    name: str
    fn: Callable
    priority: int = 0
    predicate: Optional[Predicate] = None  # hard capability constraint
    auto_predicate: Optional[Predicate] = None  # soft preference (auto only)
    doc: str = ""

    def capable(self, ctx: Ctx) -> bool:
        return self.predicate is None or bool(self.predicate(ctx))

    def preferred(self, ctx: Ctx) -> bool:
        return self.auto_predicate is None or bool(self.auto_predicate(ctx))


class KernelRegistry:
    """Name -> variant tables plus the selection/override machinery."""

    def __init__(self):
        self._variants: dict[str, dict[str, Variant]] = {}
        self._local = threading.local()

    # -- registration ------------------------------------------------------
    def register(self, kernel: str, name: str, *, priority: int = 0,
                 predicate: Optional[Predicate] = None,
                 auto_predicate: Optional[Predicate] = None,
                 doc: str = ""):
        """Decorator registering ``fn`` as implementation ``name`` of
        ``kernel``. Names are unique per kernel."""
        def deco(fn):
            table = self._variants.setdefault(kernel, {})
            if name in table:
                raise ValueError(
                    f"impl {name!r} already registered for kernel {kernel!r}")
            table[name] = Variant(kernel, name, fn, priority, predicate,
                                  auto_predicate, doc or (fn.__doc__ or ""))
            return fn
        return deco

    # -- introspection -----------------------------------------------------
    def kernels(self) -> list[str]:
        return sorted(self._variants)

    def available_impls(self, kernel: str) -> list[str]:
        """Impl names for ``kernel``, highest priority first."""
        table = self._table(kernel)
        return [v.name for v in
                sorted(table.values(), key=lambda v: (-v.priority, v.name))]

    def get(self, kernel: str, name: str) -> Variant:
        table = self._table(kernel)
        if name not in table:
            raise ValueError(
                f"unknown impl {name!r} for kernel {kernel!r}; "
                f"available: {self.available_impls(kernel)}")
        return table[name]

    def _table(self, kernel: str) -> dict[str, Variant]:
        if kernel not in self._variants:
            raise KeyError(
                f"unknown kernel {kernel!r}; registered: {self.kernels()}")
        return self._variants[kernel]

    # -- overrides ---------------------------------------------------------
    @contextmanager
    def force_impl(self, impl: str, kernel: Optional[str] = None):
        """Force ``impl`` for ``kernel`` (or for every kernel when ``None``)
        inside the ``with`` block. Nested blocks: innermost wins. For jitted
        call sites this takes effect at trace time, so wrap the first call
        (or re-jit) rather than an already-compiled function."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append((kernel, impl))
        try:
            yield
        finally:
            stack.pop()

    @contextmanager
    def record(self):
        """Yield a ``{kernel: {impl, ...}}`` dict that collects every
        resolution made inside the ``with`` block (trace time for jitted
        code). Thread-local, like :meth:`force_impl`."""
        records = getattr(self._local, "records", None)
        if records is None:
            records = self._local.records = []
        seen: dict[str, set[str]] = {}
        records.append(seen)
        try:
            yield seen
        finally:
            records.remove(seen)

    def _forced(self, kernel: str) -> Optional[tuple[str, bool]]:
        """(impl, is_global) from the innermost applicable force_impl."""
        for scope, impl in reversed(getattr(self._local, "stack", []) or []):
            if scope is None or scope == kernel:
                return impl, scope is None
        return None

    # -- selection ---------------------------------------------------------
    def resolve(self, kernel: str, impl: str = "auto",
                ctx: Optional[Ctx] = None) -> Variant:
        """Pick the variant that will run for this call context."""
        v = self._select(kernel, impl, ctx)
        for seen in getattr(self._local, "records", None) or ():
            seen.setdefault(kernel, set()).add(v.name)
        return v

    def _select(self, kernel: str, impl: str, ctx: Optional[Ctx]) -> Variant:
        table = self._table(kernel)
        full_ctx = dict(ctx or {})
        full_ctx.setdefault("on_tpu", on_tpu())

        override = self._forced(kernel)
        requested = impl
        if override is not None:
            name, is_global = override
            # a global override naming an impl this kernel doesn't have
            # (e.g. "blocked") is ignored here instead of crashing kernels
            # it was never aimed at; scoped overrides still error below
            if not (is_global and name not in table):
                requested = name
        if requested != "auto":
            v = self.get(kernel, requested)
            if v.capable(full_ctx):
                return v
            # incapable explicit request: fall back like the legacy dispatchers
            table = {n: x for n, x in table.items() if n != requested}

        ranked = sorted(table.values(), key=lambda v: (-v.priority, v.name))
        for v in ranked:
            if v.capable(full_ctx) and v.preferred(full_ctx):
                return v
        for v in ranked:
            if v.capable(full_ctx):
                return v
        raise ValueError(
            f"no capable impl for kernel {kernel!r} with ctx {full_ctx!r}")

    def dispatch(self, kernel: str, impl: str, ctx: Optional[Ctx],
                 *args, **kwargs):
        """Resolve and call in one step (the ops.py entrypoint)."""
        return self.resolve(kernel, impl, ctx).fn(*args, **kwargs)


REGISTRY = KernelRegistry()

# module-level aliases: the public API most callers want
kernel_variant = REGISTRY.register
force_impl = REGISTRY.force_impl
available_impls = REGISTRY.available_impls
