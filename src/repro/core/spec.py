"""Declarative balancing API: ``BalanceSpec`` + stage registry + ``Balancer``.

The paper's DLB step is one fixed pipeline

    keys -> partition1d -> remap -> migrate

but the implementation backends differ (host control-plane vs one jitted
shard_map region).  This module makes the pipeline *declarative*:

* ``BalanceSpec``   -- a frozen pytree-dataclass holding every knob of the
  pipeline (method, 1-D solver, k/iters, sfc bits, remap policy, backend,
  padding policy).  Hashable, serializable to/from a plain dict, and
  registered as a leaf-free pytree so it crosses ``jax.jit`` boundaries as
  static data.
* stage registry    -- pure stage functions registered per
  ``(backend, stage, variant)``; backends close over the same four stage
  names so host and sharded pipelines can never diverge structurally.
  New backends (multi-host, Pallas k-section) register variants instead
  of forking the pipeline.
* ``Balancer``      -- the facade: resolves a spec into a jit-compatible
  ``balance_fn(weights, coords, old_parts) -> BalanceResult`` plus a
  host-side ``balance()`` wrapper that applies the padding policy and an
  optional timing wrapper (wall-clock never lives inside the pipeline).

``BalanceResult`` is a pytree of device arrays -- parts, per-part weights,
imbalance, migration volume -- so it can be returned from jitted code and
consumed without host syncs.

Padded items are marked with the sentinel part id ``spec.pad_part == p``
in ``old_parts``; every similarity/migration metric masks on it (a plain
``segment_sum`` drops the out-of-range sentinel), so padding can never
skew part-0 statistics.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from . import metrics as _metrics
from . import partition1d as _p1d
from . import remap as _remap
from .rcb import rcb_partition
from .rtree import partition_dfs
from .sfc import bounding_box, sfc_keys

SFC_METHODS = ("hsfc", "msfc", "hsfc_zoltan")
METHODS = SFC_METHODS + ("rtk", "rcb", "linear")
ONED_SOLVERS = ("sorted", "ksection")
BACKENDS = ("host", "sharded")
PADDINGS = ("pow2", "none")
STAGES = ("keys", "partition1d", "remap", "migrate")


# ---------------------------------------------------------------------------
# Spec base: shared behavior of declarative frozen-dataclass specs
# ---------------------------------------------------------------------------

class Spec:
    """Mixin for frozen declarative spec dataclasses.

    Provides the contract every spec in the codebase shares
    (``BalanceSpec`` here, ``AdaptSpec`` in ``repro.fem.adapt``):

    * ``to_dict`` / ``from_dict`` -- lossless plain-dict (JSON-safe)
      round-trip, recursing into nested specs (declare them in
      ``_NESTED_SPECS``); unknown keys are rejected loudly.
    * ``replace`` -- ``dataclasses.replace`` that re-runs validation.

    Combine with ``register_spec_pytree`` so the spec crosses ``jax.jit``
    boundaries as static (leaf-free, hashable) configuration.
    """

    #: field name -> Spec subclass for nested-spec reconstruction
    _NESTED_SPECS: ClassVar[Mapping[str, type]] = {}

    def to_dict(self) -> Dict[str, Any]:
        """Plain-dict form (JSON-safe; round-trips via ``from_dict``)."""
        out: Dict[str, Any] = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            out[f.name] = v.to_dict() if isinstance(v, Spec) else v
        return out

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Spec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"unknown {cls.__name__} fields: {sorted(unknown)}")
        kw = dict(d)
        for name, sub in cls._NESTED_SPECS.items():
            if isinstance(kw.get(name), Mapping):
                kw[name] = sub.from_dict(kw[name])
        return cls(**kw)

    def replace(self, **kw) -> "Spec":
        return dataclasses.replace(self, **kw)


def register_spec_pytree(cls):
    """Register a frozen ``Spec`` dataclass as a leaf-free static pytree.

    The whole spec rides in the treedef (aux data), so jitted functions
    treat two calls with equal specs as one cache entry and specs never
    become traced values.  Usable as a class decorator."""

    def flatten(spec):
        return (), spec

    def unflatten(aux, _children):
        return aux

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


# ---------------------------------------------------------------------------
# BalanceSpec
# ---------------------------------------------------------------------------

@register_spec_pytree
@dataclasses.dataclass(frozen=True)
class BalanceSpec(Spec):
    """Declarative description of one DLB pipeline.

    Fields (old ``DynamicLoadBalancer`` kwargs map 1:1, see ROADMAP's
    migration guide):

    p                  number of parts / processes
    method             'rtk' | 'hsfc' | 'msfc' | 'hsfc_zoltan' | 'rcb'
                       | 'linear' (keys = first coordinate, or arrival
                       order when no coords -- the serving/packing case)
    oneD               1-D solver: 'sorted' (exact, one sort) or
                       'ksection' (the paper's histogram search)
    k, iters           k-section branching factor / rounds
    sfc_bits           SFC grid resolution
    use_remap          apply the Oliker--Biswas relabelling
    backend            'host' | 'sharded' (one jitted shard_map region)
    padding            host backend: 'pow2' pads to the next power-of-two
                       bucket so adaptive mesh growth reuses compiled
                       executables; 'none' passes shapes through
                       untouched.  The sharded backend ignores this and
                       always pads to p * C (shard_map needs
                       p-divisible shapes; C is a power of two >=
                       min_capacity)
    min_capacity       sharded per-device capacity floor
    execute_migration  sharded: ship payloads with the all_to_all
                       executor (False = plan-level metrics only)
    use_pallas         sharded Pallas fast paths: SFC keys kernel and,
                       with oneD='ksection', the fused per-round
                       histogram kernel (the 'ksection_pallas' stage
                       variant).  None = auto: TPU only; True forces
                       the kernel paths (their XLA forms off-TPU)
    warm_start         oneD='ksection': seed each repartition's search
                       boxes from the previous step's splitters (the
                       Balancer remembers them between calls); a single
                       validation histogram rejects stale boxes, so
                       results stay bit-identical to a cold start once
                       the search converges
    ksection_tol       stop the k-section search once every splitter box
                       is narrower than this (0 = always run ``iters``
                       rounds).  With integer keys any tol < 1 keeps the
                       converged cuts exact; combined with warm_start
                       this is what makes repartition cost track the
                       churn instead of the mesh size
    """
    p: int
    method: str = "hsfc"
    oneD: str = "sorted"
    k: int = 8
    iters: int = 12
    sfc_bits: int = 10
    use_remap: bool = True
    backend: str = "host"
    padding: str = "pow2"
    min_capacity: int = 64
    execute_migration: bool = True
    use_pallas: Optional[bool] = None
    warm_start: bool = False
    ksection_tol: float = 0.0

    def __post_init__(self):
        if self.p < 1:
            raise ValueError(f"p must be >= 1, got {self.p}")
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; "
                             f"choose from {METHODS}")
        if self.oneD not in ONED_SOLVERS:
            raise ValueError(f"unknown oneD solver {self.oneD!r}; "
                             f"choose from {ONED_SOLVERS}")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"choose from {BACKENDS}")
        if self.padding not in PADDINGS:
            raise ValueError(f"unknown padding policy {self.padding!r}; "
                             f"choose from {PADDINGS}")

    # -- identity of padded items ------------------------------------------
    @property
    def pad_part(self) -> int:
        """Sentinel part id carried by padded items in ``old_parts``.

        One past the last real part, so a ``segment_sum`` over ``p``
        segments drops it and every mask is just ``old_parts < p``.
        """
        return self.p


# ---------------------------------------------------------------------------
# BalanceResult
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BalanceResult:
    """Pytree result of one balance step -- all leaves are device arrays.

    ``total_v`` / ``max_v`` / ``retained`` are zero when no ``old_parts``
    were given; ``remap_perm`` is the identity when the remap stage did
    not run (no ``old_parts``, or ``use_remap=False``).  ``migration`` holds
    the sharded all_to_all executor's conservation scalars (weight_in,
    weight_out, items, overflow) or ``None`` when migration was not
    executed.  Wall-clock timings deliberately do not appear here: use
    ``Balancer.balance_timed`` for a host-side timing wrapper.
    """
    parts: jax.Array          # (n,) int32 part id per item
    part_weights: jax.Array   # (p,)
    imbalance: jax.Array      # () max/mean part weight
    total_v: jax.Array        # () migrated weight (TotalV)
    max_v: jax.Array          # () max per-process migrated weight (MaxV)
    retained: jax.Array       # () weight that stayed put
    remap_perm: jax.Array     # (p,) process assigned to each new part
    migration: Optional[Dict[str, jax.Array]] = None
    splitters: Optional[jax.Array] = None       # (p-1,) 1-D cuts, if any
    ksection_rounds: Optional[jax.Array] = None  # () rounds actually run


def _result_flatten(r: BalanceResult):
    return ((r.parts, r.part_weights, r.imbalance, r.total_v, r.max_v,
             r.retained, r.remap_perm, r.migration, r.splitters,
             r.ksection_rounds), None)


def _result_unflatten(_aux, ch) -> BalanceResult:
    return BalanceResult(*ch)


jax.tree_util.register_pytree_node(BalanceResult, _result_flatten,
                                   _result_unflatten)


# ---------------------------------------------------------------------------
# Stage registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[Tuple[str, str, str], Callable] = {}


def register_stage(backend: str, stage: str, variant: str) -> Callable:
    """Decorator: register a pure stage function for a backend.

    Host stage signatures::

        keys(spec, coords, weights)                  -> keys
        partition1d(spec, keys, weights, coords)     -> parts
        remap(spec, old_parts, new_parts, weights)   -> (parts, perm)
        migrate(spec, old_parts, new_parts, weights) -> dict of scalars

    Sharded stages take the same positional arguments on *local shards*
    plus a keyword ``axis`` (the mesh axis name) and run inside one
    shard_map region.
    """
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; choose from {STAGES}")

    def deco(fn):
        _REGISTRY[(backend, stage, variant)] = fn
        return fn
    return deco


def _ensure_backend_registered(backend: str) -> None:
    """Sharded stages live in ``repro.distributed.stages``; importing it
    registers them (deferred here to keep core free of a hard
    distributed-package dependency at import time)."""
    if backend == "sharded":
        from ..distributed import stages  # noqa: F401


def get_stage(backend: str, stage: str, variant: str) -> Callable:
    _ensure_backend_registered(backend)
    try:
        return _REGISTRY[(backend, stage, variant)]
    except KeyError:
        avail = stage_variants(backend, stage)
        raise ValueError(
            f"no {stage!r} stage variant {variant!r} registered for "
            f"backend {backend!r}; available: {avail}") from None


def stage_variants(backend: str, stage: str):
    """Registered variant names for (backend, stage)."""
    _ensure_backend_registered(backend)
    return sorted(v for (b, s, v) in _REGISTRY if b == backend and s == stage)


def _oneD_variant(spec: BalanceSpec) -> str:
    """1-D solver stage variant, honoring the spec's Pallas knob.

    The sharded k-section search has a fused-histogram-kernel variant
    ('ksection_pallas'); ``use_pallas=None`` auto-selects it on TPU,
    ``True`` forces it (its fused-XLA twin off-TPU), ``False`` keeps the jnp
    histogram.  Both run the identical box-shrinking search, so the
    choice never changes results -- only the per-round kernel."""
    if spec.oneD == "ksection" and spec.backend == "sharded":
        use = (jax.default_backend() == "tpu" if spec.use_pallas is None
               else spec.use_pallas)
        if use:
            return "ksection_pallas"
    return spec.oneD


def resolve_variants(spec: BalanceSpec) -> Dict[str, Optional[str]]:
    """Map a spec to the stage variants its pipeline uses.

    ``keys`` is ``None`` for direct partitioners (rtk operates on the DFS
    weight order, rcb on raw coordinates)."""
    if spec.method in SFC_METHODS:
        return {"keys": "sfc", "partition1d": _oneD_variant(spec),
                "remap": "greedy", "migrate": None}
    if spec.method == "linear":
        return {"keys": "linear", "partition1d": _oneD_variant(spec),
                "remap": "greedy", "migrate": None}
    # direct methods skip the keys stage
    return {"keys": None, "partition1d": spec.method,
            "remap": "greedy", "migrate": None}


# ---------------------------------------------------------------------------
# Host stages
# ---------------------------------------------------------------------------

@register_stage("host", "keys", "sfc")
def _keys_sfc_host(spec: BalanceSpec, coords, weights):
    curve = "morton" if spec.method == "msfc" else "hilbert"
    lo, hi = bounding_box(coords)
    return sfc_keys(coords, lo, hi, curve=curve,
                    uniform=spec.method != "hsfc_zoltan", bits=spec.sfc_bits)


@register_stage("host", "keys", "linear")
def _keys_linear_host(spec: BalanceSpec, coords, weights):
    if coords is None:
        return jnp.arange(weights.shape[0], dtype=jnp.uint32)
    return coords[:, 0]


@register_stage("host", "keys", "cached")
def _keys_cached_host(spec: BalanceSpec, coords, weights, *, keys):
    """Pass-through for precomputed keys (the incremental ``KeyCache``
    path: keys were re-keyed on the host against a frozen bounding box,
    so the in-pipeline box computation is skipped entirely)."""
    return keys


@register_stage("host", "partition1d", "sorted")
def _partition_sorted_host(spec: BalanceSpec, keys, weights, coords,
                           warm=None):
    r = _p1d.sorted_exact(keys, weights, spec.p)
    return r.parts, {"splitters": r.splitters}


@register_stage("host", "partition1d", "ksection")
def _partition_ksection_host(spec: BalanceSpec, keys, weights, coords,
                             warm=None):
    r = _p1d.ksection(keys, weights, spec.p, k=spec.k, iters=spec.iters,
                      warm=warm, tol=spec.ksection_tol)
    return r.parts, {"splitters": r.splitters, "ksection_rounds": r.rounds}


@register_stage("host", "partition1d", "rtk")
def _partition_rtk_host(spec: BalanceSpec, keys, weights, coords,
                        warm=None):
    return partition_dfs(weights, spec.p)


@register_stage("host", "partition1d", "rcb")
def _partition_rcb_host(spec: BalanceSpec, keys, weights, coords,
                        warm=None):
    return rcb_partition(coords, weights, spec.p)


@register_stage("host", "remap", "greedy")
def _remap_greedy_host(spec: BalanceSpec, old_parts, new_parts, weights):
    """Oliker--Biswas relabelling, jit-composable (device greedy solve).

    Padded items carry ``old_parts == spec.pad_part`` and fall outside the
    ``p*p`` similarity segments, so they contribute to no S entry.
    The identity guard keeps the better of greedy vs no-relabel, so a
    remap never increases migration."""
    p = spec.p
    S = _remap.similarity_matrix(old_parts, new_parts, weights, p, p)
    perm = _remap.guarded_greedy_perm(S)
    return perm[new_parts], perm


@register_stage("host", "migrate", "metrics")
def _migrate_metrics_host(spec: BalanceSpec, old_parts, new_parts, weights):
    """Plan-level migration volume (TotalV/MaxV/retained), pad-masked."""
    p = spec.p
    valid = old_parts < p
    w = jnp.where(valid, weights, 0.0)
    moved = (old_parts != new_parts) & valid
    moved_w = jnp.where(moved, w, 0.0)
    outgoing = jax.ops.segment_sum(moved_w, old_parts, num_segments=p)
    incoming = jax.ops.segment_sum(moved_w, new_parts, num_segments=p)
    return {
        "total_v": jnp.sum(moved_w),
        "max_v": jnp.maximum(jnp.max(outgoing), jnp.max(incoming)),
        "retained": jnp.sum(jnp.where(moved, 0.0, w)),
    }


# ---------------------------------------------------------------------------
# Balancer facade
# ---------------------------------------------------------------------------

class Balancer:
    """Resolve a ``BalanceSpec`` into an executable balancing pipeline.

    ``balance_fn`` is the pure pipeline -- wrap it in ``jax.jit`` (or call
    it from jitted code) on either backend.  ``balance`` applies the
    spec's padding policy, runs a cached jitted pipeline, and truncates
    the parts back to the caller's item count.  ``balance_timed`` adds a
    blocking host-side wall-clock measurement around it.
    """

    def __init__(self, spec: BalanceSpec, *, devices=None):
        self.spec = spec
        self._variants = resolve_variants(spec)
        self._jitted: Dict[Tuple[bool, bool, bool], Callable] = {}
        self._compiled: Dict[Tuple[int, bool], Callable] = {}
        # previous step's splitters, auto-threaded as warm-start boxes
        # into the next ksection call when spec.warm_start is set
        self._last_splitters: Optional[jax.Array] = None
        self.mesh = None
        if spec.backend == "sharded":
            # registers the sharded stages and builds the device mesh;
            # raises ValueError for methods with no sharded variant
            from ..distributed import stages as _stages
            self._stages_mod = _stages
            self.mesh = _stages.build_mesh(spec, devices)
            for stage in ("keys", "partition1d"):
                v = self._variants[stage]
                if v is not None:
                    get_stage("sharded", stage, v)
        else:
            for stage in ("keys", "partition1d"):
                v = self._variants[stage]
                if v is not None:
                    get_stage("host", stage, v)

    @classmethod
    def from_spec(cls, spec: BalanceSpec, *, devices=None) -> "Balancer":
        return cls(spec, devices=devices)

    # -- the pure pipeline --------------------------------------------------
    @property
    def balance_fn(self) -> Callable:
        """``(weights, coords, old_parts) -> BalanceResult``, jittable.

        Inputs must already respect the backend's shape contract (the
        ``balance`` wrapper handles that): sharded inputs have length
        ``p * C``; ``old_parts`` may be ``None`` (static).  Padded items
        carry ``spec.pad_part`` in ``old_parts``.  ``keys`` short-circuits
        the keys stage with precomputed (cached) SFC keys; ``warm`` seeds
        the k-section search boxes with a previous step's splitters."""
        # named for the program it becomes under jax.jit: jit_balance
        if self.spec.backend == "sharded":
            def balance(weights, coords, old_parts=None, keys=None,
                        warm=None):
                return self._sharded_apply(weights, coords, old_parts,
                                           keys, warm)
        else:
            def balance(weights, coords, old_parts=None, keys=None,
                        warm=None):
                return self._host_pipeline(weights, coords, old_parts,
                                           keys, warm)
        return balance

    def _host_pipeline(self, weights, coords, old_parts, pre_keys=None,
                       warm=None) -> BalanceResult:
        spec = self.spec
        p = spec.p
        kv = self._variants["keys"]
        if pre_keys is not None and kv is not None:
            keys = get_stage("host", "keys", "cached")(
                spec, coords, weights, keys=pre_keys)
        else:
            keys = (get_stage("host", "keys", kv)(spec, coords, weights)
                    if kv is not None else None)
        out = get_stage("host", "partition1d", self._variants["partition1d"])(
            spec, keys, weights, coords, warm=warm)
        new, p1d_aux = out if isinstance(out, tuple) else (out, {})
        perm = jnp.arange(p, dtype=jnp.int32)
        zero = jnp.zeros((), jnp.float32)
        total_v, max_v, retained = zero, zero, zero
        if old_parts is not None:
            if spec.use_remap:   # skipped entirely when off (O(p^3) solve)
                new, perm = get_stage("host", "remap", "greedy")(
                    spec, old_parts, new, weights)
            mv = get_stage("host", "migrate", "metrics")(
                spec, old_parts, new, weights)
            total_v, max_v, retained = (mv["total_v"], mv["max_v"],
                                        mv["retained"])
        pw = jax.ops.segment_sum(weights, new, num_segments=p)
        imb = _metrics.imbalance_of_part_weights(pw)
        return BalanceResult(parts=new, part_weights=pw, imbalance=imb,
                             total_v=total_v, max_v=max_v, retained=retained,
                             remap_perm=perm, migration=None,
                             splitters=p1d_aux.get("splitters"),
                             ksection_rounds=p1d_aux.get("ksection_rounds"))

    def _sharded_apply(self, weights, coords, old_parts, pre_keys=None,
                       warm=None) -> BalanceResult:
        has_old = old_parts is not None
        fn = self._stages_mod.build_balance_fn(
            self.spec, self.mesh, has_old,
            has_keys=pre_keys is not None, has_warm=warm is not None)
        opts = [x for x in (old_parts, pre_keys, warm) if x is not None]
        parts, aux = fn(weights, coords, *opts)
        zero = jnp.zeros((), jnp.float32)
        return BalanceResult(
            parts=parts, part_weights=aux["part_weights"],
            imbalance=aux["imbalance"],
            total_v=aux.get("total_v", zero), max_v=aux.get("max_v", zero),
            retained=aux.get("retained", zero),
            remap_perm=aux.get("remap_perm",
                               jnp.arange(self.spec.p, dtype=jnp.int32)),
            migration=aux.get("migration"),
            splitters=aux.get("splitters"),
            ksection_rounds=aux.get("ksection_rounds"))

    # -- padding policy (host-side shape management) ------------------------
    def capacity_for(self, n: int) -> int:
        """Sharded per-device capacity for an ``n``-item problem."""
        per = -(-n // self.spec.p)
        C = self.spec.min_capacity
        while C < per:
            C <<= 1
        return C

    def _pad(self, weights, coords, old_parts, keys=None):
        spec = self.spec
        n = int(weights.shape[0])
        if coords is None and spec.method in SFC_METHODS + ("rcb",):
            raise ValueError(f"method {spec.method!r} requires coords")
        w = jnp.asarray(weights, jnp.float32)
        if coords is None and spec.backend == "sharded":
            if spec.method != "linear":
                raise ValueError(
                    "sharded balance requires coords (SFC methods)")
            # sharded stages need a coords operand; linearize arrival order
            coords = jnp.stack([jnp.arange(n, dtype=jnp.float32),
                                jnp.zeros(n), jnp.zeros(n)], axis=1)
        xyz = None if coords is None else jnp.asarray(coords)
        old = None
        if old_parts is not None:
            if int(old_parts.shape[0]) != n:
                raise ValueError(
                    f"old_parts has {old_parts.shape[0]} items, weights "
                    f"{n}: after refinement, pass the inherited parts of "
                    "the *current* mesh")
            old = jnp.asarray(old_parts, jnp.int32)

        if spec.backend == "sharded":
            n_pad = spec.p * self.capacity_for(n)
        elif spec.padding == "pow2":
            n_pad = 1 << max(int(np.ceil(np.log2(max(n, 2)))), 1)
        else:
            n_pad = n
        ks = None
        if keys is not None:
            if self._variants["keys"] is None:
                raise ValueError(
                    f"method {spec.method!r} has no keys stage; "
                    "precomputed keys only apply to SFC/linear methods")
            if int(keys.shape[0]) != n:
                raise ValueError(
                    f"keys has {keys.shape[0]} items, weights {n}")
            ks = jnp.asarray(keys)
        if n_pad != n:
            w = jnp.concatenate([w, jnp.zeros(n_pad - n, w.dtype)])
            if xyz is not None:
                tail = jnp.broadcast_to(xyz[-1:], (n_pad - n, xyz.shape[1]))
                xyz = jnp.concatenate([xyz, tail])
            if old is not None:
                # sentinel part id: padded items are invisible to the
                # remap similarity and every migration metric
                old = jnp.concatenate(
                    [old, jnp.full(n_pad - n, spec.pad_part, jnp.int32)])
            if ks is not None:
                # padded items carry zero weight: their key only has to
                # keep them inside the box (repeat the last real key)
                ks = jnp.concatenate(
                    [ks, jnp.broadcast_to(ks[-1:], (n_pad - n,))])
        return w, xyz, old, ks, n

    # -- host-facing entry points -------------------------------------------
    def balance(self, weights, *, coords=None, old_parts=None, keys=None,
                warm_splitters=None) -> BalanceResult:
        """Pad per policy, run the (cached, jitted) pipeline, truncate.

        ``keys`` bypasses the keys stage with precomputed (cached) SFC
        keys.  ``warm_splitters`` seeds the k-section boxes; when
        ``spec.warm_start`` is set and it is omitted, the previous
        call's splitters are threaded automatically."""
        tr = telemetry.get_tracer()
        with tr.span("balance", block=True, backend=self.spec.backend,
                     method=self.spec.method, oneD=self.spec.oneD) as sp:
            w, xyz, old, ks, n = self._pad(weights, coords, old_parts, keys)
            warm = warm_splitters
            if warm is None and self.spec.warm_start:
                warm = self._last_splitters
            if self._variants["partition1d"] not in ("ksection",
                                                     "ksection_pallas"):
                warm = None
            if warm is not None:
                warm = jnp.asarray(warm, jnp.float32)
            sig = (old is not None, ks is not None, warm is not None)
            if sig not in self._jitted:
                self._jitted[sig] = jax.jit(self.balance_fn)
            fn = self._jitted[sig]
            if self.spec.backend == "sharded":
                # bookkeeping: jax.jit retraces per capacity bucket, so
                # each distinct (C, has_old) key is one compiled pipeline
                self._compiled[(self.capacity_for(n), sig[0])] = fn
            res = fn(w, xyz, old, ks, warm)
            if self.spec.warm_start and res.splitters is not None:
                self._last_splitters = res.splitters
            if int(res.parts.shape[0]) != n:
                res = dataclasses.replace(res, parts=res.parts[:n])
            sp.block_on(res.parts)
        if tr.enabled:
            self._publish_quality(tr, res)
        return res

    def _publish_quality(self, tr, res: BalanceResult) -> None:
        """Publish the paper's partition-quality metrics for one call.

        This is the single publication site for the balancer (host and
        sharded pipelines are bit-exact, so totals match across
        backends).  ``total_v``/``max_v``/``retained`` are zero when no
        ``old_parts`` were given, so unconditional publication is safe.
        """
        m = tr.metrics
        m.gauge("imbalance",
                help="max part weight / mean part weight").set(
                    float(res.imbalance))
        m.counter("repartitions",
                  help="balance() calls").inc()
        m.counter("migration_total_v", unit="weight",
                  help="paper TotalV: weight moved between parts").inc(
                      float(res.total_v))
        m.gauge("migration_max_v", unit="weight",
                help="paper MaxV: heaviest single-part inflow").set(
                    float(res.max_v))
        m.counter("migration_retained", unit="weight",
                  help="weight that stayed on its part").inc(
                      float(res.retained))

    def balance_timed(self, weights, *, coords=None, old_parts=None,
                      keys=None, warm_splitters=None
                      ) -> Tuple[BalanceResult, Dict[str, float]]:
        """``balance`` plus a blocking wall-clock measurement.

        The timing wrapper is the ONLY place the pipeline touches the
        host clock; the pipeline itself stays pure/jittable.  Routed
        through ``telemetry.stopwatch`` so the clock stops only after
        ``res.parts`` is device-ready, with or without a tracer."""
        with telemetry.stopwatch("balance_timed",
                                 backend=self.spec.backend) as sw:
            res = self.balance(weights, coords=coords, old_parts=old_parts,
                               keys=keys, warm_splitters=warm_splitters)
            sw.block_on(res.parts)
        return res, {"t_balance": sw.dur_s}


def compute_cut(parts, adjacency):
    """Communication proxy: element-adjacency links crossing parts.

    Companion metric kept outside ``BalanceResult`` (it needs the element
    graph, which the pure pipeline never sees)."""
    return _metrics.cut_links(parts, adjacency)
