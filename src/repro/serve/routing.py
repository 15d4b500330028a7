"""Routing policies: the serving route as a validated value object.

The engine's route decision table (``repro.serve.engine``) used to be
addressed by ad-hoc strings threaded through every caller -- a typo'd
``route="palas"`` or a kernel knob applied to the wrong route only
surfaced at dispatch time, deep inside a serving closure.  A
``RoutePolicy`` pins the whole decision down at *construction*:

========  ==============================================================
kind      meaning
========  ==============================================================
auto      backend-dependent default (merge on CPU/GPU, kernel on TPU,
          with int64 patches of the rows whose fp32 count reads >= 2^23)
merge     jitted int64 sorted-merge -- exact everywhere
table     explicit O(L^2) jnp table (eager-parity debugging)
pallas    the Pallas kernel route, with its two knobs (``block_b``,
          ``interpret``); still exactness-checked per row
sharded   multi-device replicas: index replicated, batch split over
          ``batch_axes`` of a serving mesh (merge core only)
========  ==============================================================

Kernel knobs on a non-kernel kind, a ``sharded`` policy without batch
axes, or an unknown kind all raise ``ValueError`` when the policy object
is built -- not when the first batch arrives.  Policies are frozen
(hashable, comparable) so services and configs can carry them as plain
values; ``RoutePolicy.coerce`` upgrades the legacy route strings.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Tuple

from repro.kernels.spc_query.kernel import LANES, check_block_b

#: Kinds a policy may name.  The first four map 1:1 onto the engine's
#: single-device routes; ``sharded`` selects the multi-device replica
#: path (``QueryEngine.sharded``) and needs a serving mesh at bind time.
KINDS = ("auto", "merge", "table", "pallas", "sharded")

#: Kinds that reach the Pallas kernel and may carry its knobs.
_KERNEL_KINDS = ("auto", "pallas")

_DEFAULT_BLOCK_B = LANES


@dataclasses.dataclass(frozen=True)
class RoutePolicy:
    """One validated serving-route decision (see module doc).

    Build through the classmethods (``RoutePolicy.pallas(block_b=256)``)
    or coerce a legacy string (``RoutePolicy.coerce("merge")``).
    """

    kind: str
    #: Pallas kernel row-block size (kernel kinds only): a positive
    #: multiple of 128, the vector lanes the pair batch is laid on.
    block_b: int = _DEFAULT_BLOCK_B
    #: Force/forbid kernel interpret mode; None = derive from backend at
    #: dispatch time (kernel kinds only).
    interpret: bool | None = None
    #: Mesh axes the batch is split over (``sharded`` only).
    batch_axes: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown route kind {self.kind!r}; want one of {KINDS}")
        if self.kind == "sharded":
            axes = tuple(self.batch_axes)
            if not axes or not all(isinstance(a, str) and a for a in axes):
                raise ValueError(
                    f"sharded route needs non-empty mesh axis names, got "
                    f"batch_axes={self.batch_axes!r}")
            object.__setattr__(self, "batch_axes", axes)
        elif self.batch_axes:
            raise ValueError(
                f"batch_axes only apply to the 'sharded' route, not "
                f"{self.kind!r}")
        check_block_b(self.block_b)   # a size the chip's compiler takes
        if self.kind not in _KERNEL_KINDS:
            if self.block_b != _DEFAULT_BLOCK_B or self.interpret is not None:
                raise ValueError(
                    f"block_b/interpret are Pallas kernel knobs; route "
                    f"{self.kind!r} never reaches the kernel")
        if self.interpret is not None and not isinstance(self.interpret,
                                                         bool):
            raise ValueError(
                f"interpret must be True/False/None, got "
                f"{self.interpret!r}")

    # -- constructors -------------------------------------------------------
    @classmethod
    def auto(cls, *, block_b: int = _DEFAULT_BLOCK_B,
             interpret: bool | None = None) -> "RoutePolicy":
        return cls("auto", block_b=block_b, interpret=interpret)

    @classmethod
    def merge(cls) -> "RoutePolicy":
        return cls("merge")

    @classmethod
    def table(cls) -> "RoutePolicy":
        return cls("table")

    @classmethod
    def pallas(cls, *, block_b: int = _DEFAULT_BLOCK_B,
               interpret: bool | None = None) -> "RoutePolicy":
        return cls("pallas", block_b=block_b, interpret=interpret)

    @classmethod
    def sharded(cls, batch_axes: Tuple[str, ...] = ("data",)
                ) -> "RoutePolicy":
        return cls("sharded", batch_axes=tuple(batch_axes))

    @classmethod
    def coerce(cls, route) -> "RoutePolicy":
        """Upgrade a route name (or None) to a policy; pass policies
        through.  The migration shim for the legacy string API.

        A mapping coerces too -- ``{"kind": "pallas", "block_b": 256}``
        -- so config files and front-door knobs can carry the whole
        route decision as plain data instead of only the kind string."""
        if route is None:
            return cls.auto()
        if isinstance(route, RoutePolicy):
            return route
        if isinstance(route, str):
            if route == "sharded":
                return cls.sharded()   # default batch axes
            return cls(route)  # __post_init__ validates the kind
        if isinstance(route, Mapping):
            kw = dict(route)
            kind = kw.pop("kind", "auto")
            if "batch_axes" in kw:
                kw["batch_axes"] = tuple(kw["batch_axes"])
            try:
                return cls(kind, **kw)
            except TypeError:
                known = [f.name for f in dataclasses.fields(cls)]
                raise ValueError(
                    f"route mapping has unknown keys "
                    f"{sorted(set(kw) - set(known))}; want a subset of "
                    f"{known}") from None
        raise ValueError(
            f"route must be a RoutePolicy or one of {KINDS}, got "
            f"{type(route).__name__} {route!r}")

    # -- engine binding -----------------------------------------------------
    @property
    def needs_mesh(self) -> bool:
        """True when binding this policy requires a serving mesh."""
        return self.kind == "sharded"

    @property
    def engine_route(self) -> str:
        """The single-device engine route evaluating this policy's
        batches (the sharded replica path only shards the merge core)."""
        return "merge" if self.kind == "sharded" else self.kind
