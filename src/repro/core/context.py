"""Computational context: recipes, keys, and materialised state.

The paper (§5.2) defines a *computational context* as the reusable state a
task needs before any useful work happens, with four elements: the
function's code, its software dependencies, the context code, and the
context inputs.  We model each element as a :class:`ContextElement` with a
content hash and a byte size, so the management layer (registry, transfer
planner, cache) can reason about identity and placement without caring what
the element *is*.

TPU adaptation (DESIGN.md §2): we add a fifth element the paper could not
have — the compiled XLA executable.  On TPUs, ``jit`` compilation of a
model step is O(10-100 s), the same order as weight staging, so the compile
cache participates in context management as a first-class element keyed by
(config, shapes, mesh).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, Optional, Tuple


class Tier(str, Enum):
    """Where a materialised context element lives (paper: disk/memory/GPU)."""
    DISK = "disk"
    HOST = "host"
    DEVICE = "device"

    @property
    def order(self) -> int:
        return {"disk": 0, "host": 1, "device": 2}[self.value]


def content_hash(*parts: Any) -> str:
    """Stable content hash over json-serialisable parts."""
    h = hashlib.sha256()
    for p in parts:
        h.update(json.dumps(p, sort_keys=True, default=str).encode())
    return h.hexdigest()[:16]


@dataclass(frozen=True)
class ContextElement:
    """One element of a context recipe.

    ``loader`` (live mode only) materialises the element; in sim mode the
    byte sizes alone drive staging/transfer costs.
    """
    name: str                       # "deps" | "weights" | "code" | ...
    nbytes_disk: int                # size as staged on disk (packed)
    nbytes_host: int = 0            # resident host-memory size (0 = same)
    nbytes_device: int = 0          # accelerator bytes (0 = not device-resident)
    version: str = "0"
    loader: Optional[Callable[[], Any]] = field(
        default=None, compare=False, hash=False)

    @property
    def key(self) -> str:
        return content_hash(self.name, self.nbytes_disk, self.version)

    def nbytes(self, tier: Tier) -> int:
        if tier is Tier.DISK:
            return self.nbytes_disk
        if tier is Tier.HOST:
            return self.nbytes_host or self.nbytes_disk
        return self.nbytes_device

    @property
    def home(self) -> Tier:
        """Residency tier at which this element is fully materialised."""
        if self.nbytes_device:
            return Tier.DEVICE
        if self.nbytes_host or self.nbytes_disk:
            return Tier.HOST
        return Tier.DISK


def resident_footprint(elements, tier: Tier) -> int:
    """Bytes a set of (deduplicated) elements occupies at ``tier`` when each
    is fully resident at its home tier (an element resident at DEVICE keeps
    its HOST and DISK staging copies — same accounting as ContextCache)."""
    return sum(e.nbytes(tier) for e in elements
               if tier.order <= e.home.order)


# Decode-state (KV cache + activations) bytes one admitted request holds on
# the accelerator, as a fraction of the model's active-parameter count.  Used
# to derive a library's continuous-batching slot budget when the recipe does
# not pin an explicit ``slot_bytes``.
KV_BYTES_PER_PARAM = 0.25

# Live-measured per-slot decode-state bytes, keyed by recipe key: the live
# executor records the REAL cache footprint (jax.Array.nbytes over the slot
# pool's cache pytree / capacity) after the first admission prefill, and
# every ContextRecipe instance with the same key sees it — replacing the
# KV_BYTES_PER_PARAM analytic guess for slot budgets (ROADMAP: slot budgets
# from measured memory).
_MEASURED_SLOT_BYTES: Dict[str, int] = {}
# One library never grows its dynamic batch past this many slots, regardless
# of free device memory (straggler/jitter control, same spirit as vLLM's
# max_num_seqs).
MAX_BATCH_SLOTS = 32


@dataclass(frozen=True)
class ContextRecipe:
    """The full recipe for a function's context (paper §5.3.1).

    ``elements`` ordering is the staging order: software deps must land
    before weights can be deserialised, weights before the compiled step
    can run, etc.
    """
    fn_name: str
    elements: Tuple[ContextElement, ...]
    # static per-activation cost in seconds (fork-exec of the library
    # process, import time) paid once per worker even with a warm cache:
    activation_s: float = 0.0
    # device bytes ONE admitted request occupies while decoding (KV cache,
    # sampling state).  0 = derive from active params via KV_BYTES_PER_PARAM.
    slot_bytes: int = 0

    @property
    def key(self) -> str:
        k = self.__dict__.get("_key")      # memoised: hot in scheduler loops
        if k is None:
            k = content_hash(self.fn_name, [e.key for e in self.elements])
            object.__setattr__(self, "_key", k)
        return k

    def element(self, name: str) -> ContextElement:
        for e in self.elements:
            if e.name == name:
                return e
        raise KeyError(name)

    def nbytes(self, tier: Tier) -> int:
        return sum(e.nbytes(tier) for e in self.elements)

    @property
    def transfer_bytes(self) -> int:
        """Bytes that move over the network when peer-transferring."""
        return self.nbytes(Tier.DISK)

    def decode_slot_bytes(self, active_params: float) -> int:
        """Device bytes one in-flight request pins while decoding.

        Preference order: an explicit ``slot_bytes`` pin, then the
        live-measured per-slot footprint (``record_slot_bytes``), then the
        ``KV_BYTES_PER_PARAM`` analytic estimate.

        Under the PAGED KV layout this is a per-request PAGE BUDGET: the
        decoder measures ``max_pages * page_bytes`` — the worst case one
        request can pin with a fully private ring — so admission keeps
        its simple bytes-per-slot arithmetic.  Shared-prefix pages are
        refcounted and counted once, so actual residency is at most (and
        with any prefix reuse strictly below) slots × this figure; the
        slack is intentional headroom, never an over-commit."""
        if self.slot_bytes:
            return self.slot_bytes
        measured = _MEASURED_SLOT_BYTES.get(self.key)
        if measured:
            return measured
        return max(int(active_params * KV_BYTES_PER_PARAM), 1)

    def record_slot_bytes(self, nbytes: int) -> None:
        """Feed back a live-measured per-slot decode footprint (bytes).

        Latest measurement wins: the figure is the measuring pool's
        worst-case page allotment per row (``max_pages * page_bytes``,
        set by its ``max_len``), so a decoder re-built with a longer ring
        simply re-records once its cache exists."""
        if nbytes > 0:
            _MEASURED_SLOT_BYTES[self.key] = int(nbytes)

    @property
    def measured_slot_bytes(self) -> int:
        return _MEASURED_SLOT_BYTES.get(self.key, 0)

    def with_elements(self, *extra: ContextElement) -> "ContextRecipe":
        return dataclasses.replace(self, elements=self.elements + extra)


@dataclass
class MaterializedContext:
    """A recipe realised on a worker: per-element tier + live payloads."""
    recipe: ContextRecipe
    tiers: Dict[str, Tier] = field(default_factory=dict)
    payloads: Dict[str, Any] = field(default_factory=dict)   # live mode

    @property
    def key(self) -> str:
        return self.recipe.key

    def tier_of(self, name: str) -> Optional[Tier]:
        return self.tiers.get(name)

    @property
    def fully_resident(self) -> bool:
        """Every element at its home tier (device if it has device bytes)."""
        for e in self.recipe.elements:
            t = self.tiers.get(e.name)
            if t is None:
                return False
            home = Tier.DEVICE if e.nbytes_device else Tier.HOST
            if t.order < home.order:
                return False
        return True

    def nbytes(self, tier: Tier) -> int:
        """Bytes this context occupies *at* a tier on the worker."""
        total = 0
        for e in self.recipe.elements:
            t = self.tiers.get(e.name)
            if t is None:
                continue
            # an element resident at HOST also keeps its DISK copy (cache);
            # a DEVICE-resident element keeps HOST+DISK staging copies.
            if tier.order <= t.order:
                total += e.nbytes(tier)
        return total


# ---------------------------------------------------------------------------
# Recipe builders
# ---------------------------------------------------------------------------

def model_context_recipe(cfg, *, include_compile: bool = True,
                         shapes_key: str = "", mesh_key: str = "",
                         deps_bytes: int = 3_700_000_000,
                         activation_s: float = 2.0) -> ContextRecipe:
    """Recipe for an LLM inference context from a :class:`ModelConfig`.

    Mirrors the paper's measured artefacts for SmolLM2-1.7B: a 3.7 GB
    Poncho dependency package, 3.7 GB of weights on disk and ~7.4 GB of
    host memory when loaded (fp32 upcast), plus the device copy.
    """
    n_params = cfg.n_params()
    bytes_per_param = 2 if cfg.dtype == "bfloat16" else 4
    w_disk = n_params * bytes_per_param
    elements = [
        ContextElement("deps", nbytes_disk=deps_bytes,
                       nbytes_host=512_000_000,   # import footprint, not pkg
                       version="conda-308pkg"),
        ContextElement("code", nbytes_disk=65_536, version=cfg.arch_id),
        ContextElement("weights", nbytes_disk=w_disk,
                       nbytes_host=2 * w_disk,          # deserialise + cast
                       nbytes_device=w_disk,
                       version=cfg.arch_id),
        ContextElement("context_inputs", nbytes_disk=4_194_304,
                       version="prompt-template+db"),
    ]
    if include_compile:
        elements.append(ContextElement(
            "xla_executable", nbytes_disk=256_000_000,
            nbytes_device=64_000_000,
            version=content_hash(cfg.arch_id, shapes_key, mesh_key)))
    return ContextRecipe(fn_name=f"infer::{cfg.arch_id}",
                         elements=tuple(elements),
                         activation_s=activation_s)


def partial_context_recipe(cfg, **kw) -> ContextRecipe:
    """The paper's *partial context*: software deps + weights only (pv2/pv3).

    Context code/inputs and the compiled step are NOT registered, so every
    task re-runs model load + compile even on a warm worker.
    """
    full = model_context_recipe(cfg, include_compile=False, **kw)
    keep = tuple(e for e in full.elements if e.name in ("deps", "weights"))
    return dataclasses.replace(full, elements=keep,
                               fn_name=f"partial::{cfg.arch_id}")
