"""Declarative adaptive-FEM engine: ``AdaptSpec`` + ``AdaptiveSession``.

The paper's computation model per adaptive step:

    solve -> estimate -> mark -> refine(/coarsen) -> **balance** -> repeat

PR 2 made the *balance* stage declarative (``repro.core.BalanceSpec`` +
stage registry + ``Balancer``).  This module extends the same design one
level up, to the loop that drives it:

* ``AdaptSpec``       -- a frozen ``Spec`` dataclass describing the whole
  loop: problem name (resolved through ``repro.fem.problems``), marking
  (Dörfler ``theta`` / ``coarsen_frac``), repartition trigger policy,
  the nested ``balance: BalanceSpec``, backend, size/step limits, and
  time stepping (``dt``/``n_steps``; ``dt == 0`` means stationary).
  Hashable, leaf-free pytree, plain-dict round-trip (nested spec
  included).
* stage registry      -- loop stages registered per ``(stage, variant)``:
  ``solve`` ('stationary' | 'backward_euler', plus '_owned' twins that
  run distributed PCG on owner-sharded vertices via the halo exchange),
  ``estimate`` ('zz'), ``mark`` ('doerfler'), ``adapt_mesh`` ('refine' |
  'coarsen_refine'), ``transfer`` ('p1'), ``balance`` ('host' |
  'sharded').  New physics or backends register variants instead of
  forking the driver.
* ``AdaptiveSession`` -- resolves a spec into stage functions, runs the
  loop template for the problem kind, centralizes per-stage wall-clock
  timing and ``StepStats`` emission, and invokes user hooks
  (``on_step`` / ``on_stage``).

The repartition trigger is the paper's: rebalance only when the inherited
partition's load imbalance exceeds a threshold (``trigger='imbalance'``),
or every step / only once (``'always'`` / ``'never'``); the number of
repartitionings is reported (paper Table 1).  The previous partition is
threaded into every balance call, so the Oliker--Biswas remap and the
migration metrics are live on both the stationary and the time-dependent
loop (the old parabolic driver dropped ``old_parts`` -- fixed here by
construction).

``backend='sharded'`` resolves the nested ``BalanceSpec`` onto the
on-device pipeline and adds the element-payload resharding
(``fem.parallel.shard_elements_on_device``) to the balance stage, so the
refined mesh's payloads migrate between devices with the executor's
``all_to_all`` after every repartition.  ``vertex_layout='owned'``
additionally rebuilds the owned-vertex ``fem.halo.HaloPlan`` from every
new partition (the ghost sets change whenever the cut does) and swaps
the solve stage for the halo-exchange distributed PCG, so the loop runs
end-to-end without any vertex-sized global collective; the per-matvec
wire-volume model (psum vs halo bytes vs surface index) lands in
``StepStats``.

``solve_helmholtz_adaptive`` / ``solve_parabolic_adaptive`` remain as
deprecated thin wrappers that build a spec and delegate to the session.
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, ClassVar, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import deprecation, telemetry
from ..core import Balancer, BalanceSpec, imbalance
from ..core.metrics import cut_links
from ..core.sfc import refresh_key_cache
from ..core.spec import SFC_METHODS, Spec, register_spec_pytree
from .assemble import build_elements, load_vector, mass_matvec
from .estimate import doerfler_mark, threshold_coarsen_mark, zz_estimate
from .mesh import Mesh
from .problems import ParabolicProblem, ProblemSetup, get_problem
from .refine import coarsen, refine
from .solve import solve_dirichlet

from .parallel import VERTEX_LAYOUTS

ADAPT_STAGES = ("solve", "estimate", "mark", "adapt_mesh", "transfer",
                "balance")
TRIGGERS = ("imbalance", "always", "never")
ADAPT_BACKENDS = ("host", "sharded")


# ---------------------------------------------------------------------------
# Per-step records
# ---------------------------------------------------------------------------

@dataclass
class StepStats:
    n_tets: int
    n_verts: int
    eta: float
    err_l2: Optional[float]
    cg_iters: int
    t_solve: float
    t_estimate: float
    t_refine: float
    t_balance: float
    imbalance: float
    repartitioned: bool
    migration_totalv: float = 0.0
    cut: Optional[int] = None
    migration_retained: float = 0.0
    t_transfer: float = 0.0
    # communication-volume model per matvec (vertex_layout='owned' only):
    # replicated-path psum bytes vs halo-exchange bytes; cut above is the
    # surface index the halo bytes scale with
    comm_psum_bytes: int = 0
    comm_halo_bytes: int = 0


@dataclass
class AdaptiveResult:
    stats: List[StepStats] = field(default_factory=list)
    n_repartitions: int = 0
    u: Optional[jax.Array] = None
    mesh: Optional[Mesh] = None
    # backend='sharded': the latest on-device (p, C, ...) element packing
    # produced by fem.parallel.shard_elements_on_device after balancing
    sharded: Optional[object] = None
    # vertex_layout='owned': the HaloPlan matching ``sharded``
    halo: Optional[object] = None
    spec: Optional["AdaptSpec"] = None


# ---------------------------------------------------------------------------
# AdaptSpec
# ---------------------------------------------------------------------------

@register_spec_pytree
@dataclass(frozen=True)
class AdaptSpec(Spec):
    """Declarative description of one adaptive solve.

    Fields (old driver kwargs map 1:1, see ROADMAP's migration guide):

    problem            registered problem name ('helmholtz', 'parabolic',
                       or anything added via ``fem.problems
                       .register_problem``); selects physics, the solve
                       variant (stationary vs backward Euler), and the
                       default mesh
    theta              Dörfler bulk-marking fraction
    coarsen_frac       time-dependent loop: coarsen elements with
                       ``eta < coarsen_frac * mean(eta)`` before refining
    estimate, mark     stage variant names (extensible via
                       ``register_adapt_stage``)
    solve              solve variant; 'auto' resolves from the problem
                       kind ('stationary' | 'backward_euler')
    trigger            repartition policy: 'imbalance' (the paper's --
                       repartition when the inherited partition exceeds
                       ``imbalance_trigger``), 'always', or 'never'
                       (partition once at the first step, then keep it)
    balance            nested ``repro.core.BalanceSpec``; its ``backend``
                       is overridden by this spec's ``backend``
    backend            'host' | 'sharded' (on-device balance pipeline +
                       element-payload resharding per step)
    vertex_layout      'replicated' | 'owned' (sharded backend only):
                       'owned' shards vertices by owner part -- the
                       balance stage derives a ``fem.halo.HaloPlan`` from
                       every new partition and the solve runs distributed
                       PCG whose matvec communicates via the neighbor
                       halo exchange instead of a global psum
    incremental        make rebalance cost scale with the per-step delta:
                       SFC keys are cached on the leaf payload and only
                       dirty leaves re-key (frozen bounding box with a
                       drift invalidation rule), the k-section search is
                       warm-started from the previous step's splitters,
                       and the owned-layout ``HaloPlan`` is rebuilt from
                       the refinement/migration delta instead of from
                       scratch.  Every path is exact vs the cold rebuild
                       (same frozen box, converged boxes)
    max_steps          stationary: adaptive iterations
    max_tets           stop refining beyond this many elements
    dt, n_steps        time stepping (backward Euler); ``dt == 0`` means
                       stationary and ``n_steps`` must be 0
    tol, maxiter       PCG stopping criteria
    """
    problem: str = "helmholtz"
    theta: float = 0.5
    coarsen_frac: float = 0.0
    estimate: str = "zz"
    mark: str = "doerfler"
    solve: str = "auto"
    trigger: str = "imbalance"
    imbalance_trigger: float = 1.05
    balance: BalanceSpec = BalanceSpec(p=16, method="hsfc")
    backend: str = "host"
    vertex_layout: str = "replicated"
    incremental: bool = False
    max_steps: int = 10
    max_tets: int = 200_000
    dt: float = 0.0
    n_steps: int = 0
    tol: float = 1e-8
    maxiter: int = 2000

    _NESTED_SPECS: ClassVar[Mapping[str, type]] = {"balance": BalanceSpec}

    def __post_init__(self):
        if not isinstance(self.balance, BalanceSpec):
            raise ValueError("balance must be a BalanceSpec (got "
                             f"{type(self.balance).__name__})")
        if self.trigger not in TRIGGERS:
            raise ValueError(f"unknown trigger {self.trigger!r}; "
                             f"choose from {TRIGGERS}")
        if self.backend not in ADAPT_BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"choose from {ADAPT_BACKENDS}")
        if self.vertex_layout not in VERTEX_LAYOUTS:
            raise ValueError(
                f"unknown vertex_layout {self.vertex_layout!r}; "
                f"choose from {VERTEX_LAYOUTS}")
        if self.vertex_layout == "owned" and self.backend != "sharded":
            raise ValueError("vertex_layout='owned' needs backend='sharded' "
                             "(the halo exchange lives on the device mesh)")
        if not 0.0 < self.theta <= 1.0:
            raise ValueError(f"theta must be in (0, 1], got {self.theta}")
        if self.coarsen_frac < 0.0:
            raise ValueError("coarsen_frac must be >= 0")
        if self.dt < 0.0:
            raise ValueError("dt must be >= 0 (0 means stationary)")
        if self.dt > 0.0 and self.n_steps < 1:
            raise ValueError("time-dependent spec (dt > 0) needs n_steps >= 1")
        if self.dt == 0.0 and self.n_steps != 0:
            raise ValueError("n_steps is only meaningful with dt > 0; "
                             "stationary specs use max_steps")
        if self.dt == 0.0 and self.max_steps < 1:
            raise ValueError("max_steps must be >= 1")

    @property
    def stationary(self) -> bool:
        return self.dt == 0.0

    @property
    def p(self) -> int:
        """Number of parts / simulated processes (from the nested spec)."""
        return self.balance.p

    @classmethod
    def for_problem(cls, name: str, **overrides) -> "AdaptSpec":
        """Spec seeded from a registered problem's paper defaults.

        Pulls ``theta`` / ``coarsen_frac`` / ``max_tets`` from the
        ``ProblemSetup``; parabolic problems additionally default to
        ``trigger='always'`` with ``dt=0.01, n_steps=20`` (the paper's
        Example 3.2 configuration).  Any field can be overridden."""
        setup = get_problem(name)
        kw: Dict[str, Any] = dict(problem=name, theta=setup.theta,
                                  coarsen_frac=setup.coarsen_frac,
                                  max_tets=setup.max_tets)
        if setup.kind == "parabolic":
            kw.update(trigger="always", dt=0.01, n_steps=20)
        kw.update(overrides)
        return cls(**kw)


# ---------------------------------------------------------------------------
# Stage registry (mirrors repro.core.spec's (backend, stage, variant) one)
# ---------------------------------------------------------------------------

_ADAPT_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def register_adapt_stage(stage: str, variant: str) -> Callable:
    """Decorator: register a loop-stage function under ``(stage, variant)``.

    Stage functions take ``(session, state)`` and mutate the
    ``SessionState`` in place; the session owns timing and ordering.
    """
    if stage not in ADAPT_STAGES:
        raise ValueError(f"unknown adapt stage {stage!r}; "
                         f"choose from {ADAPT_STAGES}")

    def deco(fn):
        _ADAPT_REGISTRY[(stage, variant)] = fn
        return fn
    return deco


def get_adapt_stage(stage: str, variant: str) -> Callable:
    try:
        return _ADAPT_REGISTRY[(stage, variant)]
    except KeyError:
        avail = adapt_stage_variants(stage)
        raise ValueError(
            f"no {stage!r} stage variant {variant!r} registered; "
            f"available: {avail}") from None


def adapt_stage_variants(stage: str):
    """Registered variant names for an adapt-loop stage."""
    return sorted(v for (s, v) in _ADAPT_REGISTRY if s == stage)


def resolve_adapt_variants(spec: AdaptSpec,
                           setup: Optional[ProblemSetup] = None
                           ) -> Dict[str, Optional[str]]:
    """Map a spec to the stage variants its loop uses.

    ``transfer`` is ``None`` for stationary problems (nothing to carry
    between meshes); the time-dependent loop folds estimate+mark into its
    ``adapt_mesh`` variant but still resolves them for the nested calls.
    """
    if setup is None:
        setup = get_problem(spec.problem)
    solve = spec.solve
    if solve == "auto":
        solve = ("stationary" if setup.kind == "stationary"
                 else "backward_euler")
        if spec.backend == "sharded" and spec.vertex_layout == "owned":
            solve += "_owned"
    stationary = setup.kind == "stationary"
    return {
        "solve": solve,
        "estimate": spec.estimate,
        "mark": spec.mark,
        "adapt_mesh": "refine" if stationary else "coarsen_refine",
        "transfer": None if stationary else "p1",
        "balance": spec.backend,
    }


# ---------------------------------------------------------------------------
# Session state
# ---------------------------------------------------------------------------

@dataclass
class SessionState:
    """Mutable per-run state threaded through the stage functions."""
    mesh: Mesh
    step: int = 0
    t: float = 0.0                      # physical time (time-dependent)
    el: Any = None                      # P1Elements of the current mesh
    u: Any = None                       # nodal solution on the current mesh
    eta: Optional[np.ndarray] = None    # per-element error indicators
    marked: Optional[np.ndarray] = None
    active_before: Optional[np.ndarray] = None   # pre-refine vertex mask
    grew: bool = True
    cg_iters: int = 0
    err_l2: Optional[float] = None
    repartitioned: bool = False
    step_imbalance: float = float("nan")
    migration_totalv: float = 0.0
    migration_retained: float = 0.0
    balance_result: Any = None          # core.BalanceResult of last repart
    sharded: Any = None                 # latest ShardedElements (sharded)
    halo: Any = None                    # HaloPlan matching `sharded` (owned)
    # connectivity/partition snapshots `halo` was built from, so the
    # incremental session can rebuild the next plan from the delta
    packed_tets: Optional[np.ndarray] = None
    packed_parts: Optional[np.ndarray] = None
    halo_info: Optional[Dict] = None    # how the last HaloPlan was produced
    key_info: Optional[Dict] = None     # how the last SFC keys were produced
    # staleness tracking for the owned packing: the adapt_mesh stages bump
    # mesh_version on every mutation (counts alone can't tell a
    # coarsen+refine step that keeps n_tets/n_verts constant from a no-op)
    mesh_version: int = 0
    packed_version: int = -1            # mesh_version `sharded` was packed at
    packed_ntets: int = -1              # n_tets `sharded` was packed for
    balanced_step: int = -1             # step _balance_common last ran on
    owned_ops: Dict[float, Any] = field(default_factory=dict)  # c -> operators
    cut: Optional[int] = None           # surface index of current partition
    comm_psum_bytes: int = 0            # per-matvec comm model (owned)
    comm_halo_bytes: int = 0
    timings: Dict[str, float] = field(default_factory=dict)

    @property
    def parts(self) -> Optional[np.ndarray]:
        """Current element partition (propagated through refine/coarsen)."""
        return self.mesh.leaf_payload.get("parts")


def _ensure_elements(state: SessionState):
    """(Re)build P1 element arrays iff the cached ones are stale."""
    el = state.el
    if el is None or int(el.tets.shape[0]) != state.mesh.n_tets:
        state.el = build_elements(state.mesh.verts, state.mesh.tets)
    return state.el


def _free_mask(mesh: Mesh) -> jax.Array:
    free = np.ones(mesh.n_verts, np.float64)
    free[mesh.boundary_vertices()] = 0.0
    return jnp.asarray(free)


def _l2_error(el, verts, u, exact) -> float:
    xq = verts[np.asarray(el.tets)]
    uq = np.asarray(u)[np.asarray(el.tets)]       # (nt, 4)
    ue = np.asarray(exact(jnp.asarray(xq.reshape(-1, 3)))).reshape(uq.shape)
    vol = np.asarray(el.vol)
    # vertex rule
    return float(np.sqrt((((uq - ue) ** 2).mean(axis=1) * vol).sum()))


# ---------------------------------------------------------------------------
# Stage implementations
# ---------------------------------------------------------------------------

@register_adapt_stage("solve", "stationary")
def _solve_stationary(session: "AdaptiveSession", state: SessionState):
    """One Dirichlet solve of ``-Delta u + c u = f`` on the current mesh."""
    prob = session.problem
    el = _ensure_elements(state)
    verts = jnp.asarray(state.mesh.verts)
    rhs = load_vector(el, verts, prob.f)
    sol = solve_dirichlet(el, rhs, prob.exact(verts), _free_mask(state.mesh),
                          prob.c, tol=session.spec.tol,
                          maxiter=session.spec.maxiter)
    state.u = jax.block_until_ready(sol.x)
    state.cg_iters = int(sol.iters)


@register_adapt_stage("solve", "backward_euler")
def _solve_backward_euler(session: "AdaptiveSession", state: SessionState):
    """One backward-Euler step ``(M/dt + A) u = M u_prev/dt + f(t+dt)``."""
    prob = session.problem
    spec = session.spec
    t_next = state.t + spec.dt
    el = _ensure_elements(state)
    verts = jnp.asarray(state.mesh.verts)
    fv = load_vector(el, verts, lambda x: prob.f(x, t_next))
    rhs = mass_matvec(el, jnp.asarray(state.u)) / spec.dt + fv
    sol = solve_dirichlet(el, rhs, prob.exact(verts, t_next),
                          _free_mask(state.mesh), 1.0 / spec.dt,
                          tol=spec.tol, maxiter=spec.maxiter)
    state.u = jax.block_until_ready(sol.x)
    state.cg_iters = int(sol.iters)


def _pack_owned(session: "AdaptiveSession", state: SessionState):
    """Owned-layout packing from the current mesh + partition: build the
    ``HaloPlan``, migrate/renumber element payloads on device, record the
    per-matvec communication model, and invalidate the cached operators.
    The single packing recipe -- both the balance stage and the solve-path
    staleness repack go through here."""
    from .halo import build_halo_plan, publish_wire_model, update_halo_plan
    from .parallel import shard_elements_on_device
    with telemetry.span("adapt/pack", step=state.step):
        el = _ensure_elements(state)
        mesh = state.mesh
        parts = np.asarray(mesh.leaf_payload["parts"])
        p = session.balance_spec.p
        step = state.step
        with telemetry.span("adapt/pack/halo_plan", step=step):
            if (session.spec.incremental and state.halo is not None
                    and state.packed_tets is not None
                    and state.packed_parts is not None):
                plan, hinfo = update_halo_plan(
                    state.halo, state.packed_tets, state.packed_parts,
                    mesh.tets, parts, mesh.n_verts, p)
            else:
                plan = build_halo_plan(mesh.tets, parts, mesh.n_verts, p)
                hinfo = {"mode": "scratch"}
        state.halo = plan
        state.halo_info = hinfo
        state.packed_tets = mesh.tets.copy()
        state.packed_parts = parts.copy()
        with telemetry.span("adapt/pack/migrate", step=step):
            state.sharded = shard_elements_on_device(
                el, jnp.asarray(parts), p, session.device_mesh, halo=plan)
        state.packed_ntets = mesh.n_tets
        state.packed_version = state.mesh_version
        state.owned_ops = {}
        with telemetry.span("adapt/pack/cut", step=step):
            state.cut = int(cut_links(jnp.asarray(parts),
                                      jnp.asarray(mesh.face_adjacency())))
        # wire bytes in the solve's actual scalar width (f32 by default, f64
        # under jax_enable_x64) -- the element arrays carry that dtype
        itemsize = int(np.dtype(el.vol.dtype).itemsize)
        state.comm_psum_bytes = plan.psum_bytes(itemsize)
        state.comm_halo_bytes = plan.halo_bytes(itemsize)
        tr = telemetry.get_tracer()
        if tr.enabled:
            publish_wire_model(plan, tr.metrics, itemsize=itemsize)


def _ensure_owned_packing(session: "AdaptiveSession", state: SessionState):
    """(Re)build the owned-layout packing + halo plan iff stale.

    Fresh after the previous step's sharded balance stage on the
    stationary loop; the time-dependent loop adapts the mesh *before*
    solving (``mesh_version`` moved on), so the inherited
    (propagated-through-coarsen/refine) partition re-packs here.  The
    first step, with no partition at all, runs the balance policy once --
    the end-of-step balance stage then sees it as inherited."""
    el = _ensure_elements(state)
    mesh = state.mesh
    if (state.halo is not None and state.sharded is not None
            and state.sharded.layout == "owned"
            and state.packed_version == state.mesh_version
            and state.halo.n_verts == el.n_verts
            and state.packed_ntets == mesh.n_tets):
        return
    parts = state.parts
    if parts is None or len(parts) != mesh.n_tets:
        _balance_common(session, state)
    _pack_owned(session, state)


def _owned_operators(session: "AdaptiveSession", state: SessionState,
                     c: float):
    """Cached ``OwnedOperators`` for the current packing -- rebuilt only
    when the packing itself is (``_pack_owned`` clears the cache)."""
    from .parallel import make_owned_operators
    ops = state.owned_ops.get(c)
    if ops is None:
        # kernel selection reuses PR 4's variant seam: the nested
        # BalanceSpec's use_pallas flag (None = auto on TPU)
        with telemetry.span("adapt/solve/operators", step=state.step):
            ops = make_owned_operators(
                state.sharded, session.device_mesh, c,
                use_pallas=session.balance_spec.use_pallas)
        state.owned_ops[c] = ops
    return ops


def _solve_owned(session: "AdaptiveSession", state: SessionState,
                 rhs_g_free: Callable, c: float):
    """The owned solve stages' shared tail: assemble the (n_verts,) load
    vector, boundary values and free mask with ``rhs_g_free(el, verts)``,
    run the distributed PCG, and wait for its solution -- each phase under
    its own span."""
    from .parallel import sharded_solve_dirichlet
    step = state.step
    el = _ensure_elements(state)
    _ensure_owned_packing(session, state)
    with telemetry.span("adapt/solve/assemble", step=step):
        rhs, g, free = rhs_g_free(el, jnp.asarray(state.mesh.verts))
    ops = _owned_operators(session, state, c)
    with telemetry.span("adapt/solve/pcg", step=step):
        sol = sharded_solve_dirichlet(
            state.sharded, session.device_mesh, rhs, g, free, c,
            tol=session.spec.tol, maxiter=session.spec.maxiter,
            operators=ops)
    with telemetry.span("adapt/solve/wait", step=step):
        state.u = jax.block_until_ready(sol.x)
        state.cg_iters = int(sol.iters)


@register_adapt_stage("solve", "stationary_owned")
def _solve_stationary_owned(session: "AdaptiveSession", state: SessionState):
    """Stationary solve on owned vertices: distributed PCG whose matvec
    communicates via the halo exchange (no vertex-sized psum)."""
    prob = session.problem

    def rhs_g_free(el, verts):
        return (load_vector(el, verts, prob.f), prob.exact(verts),
                _free_mask(state.mesh))

    _solve_owned(session, state, rhs_g_free, prob.c)


@register_adapt_stage("solve", "backward_euler_owned")
def _solve_backward_euler_owned(session: "AdaptiveSession",
                                state: SessionState):
    """Backward-Euler step on owned vertices (same system as the
    replicated variant, halo-exchange matvec)."""
    prob = session.problem
    dt = session.spec.dt
    t_next = state.t + dt

    def rhs_g_free(el, verts):
        fv = load_vector(el, verts, lambda x: prob.f(x, t_next))
        rhs = mass_matvec(el, jnp.asarray(state.u)) / dt + fv
        return rhs, prob.exact(verts, t_next), _free_mask(state.mesh)

    _solve_owned(session, state, rhs_g_free, 1.0 / dt)


@register_adapt_stage("estimate", "zz")
def _estimate_zz(session: "AdaptiveSession", state: SessionState):
    """Zienkiewicz--Zhu gradient-recovery indicators for the current u."""
    el = _ensure_elements(state)
    state.eta = np.asarray(jax.block_until_ready(
        zz_estimate(el, jnp.asarray(state.u))))


@register_adapt_stage("mark", "doerfler")
def _mark_doerfler(session: "AdaptiveSession", state: SessionState):
    state.marked = doerfler_mark(state.eta, session.spec.theta)


@register_adapt_stage("adapt_mesh", "refine")
def _adapt_refine(session: "AdaptiveSession", state: SessionState):
    """Stationary loop: refine the marked set (no coarsening).

    The final step and the ``max_tets`` ceiling skip refinement so the
    reported solution lives on the solved mesh."""
    spec = session.spec
    state.grew = False
    last = spec.stationary and state.step >= spec.max_steps - 1
    if state.mesh.n_tets < spec.max_tets and not last:
        refine(state.mesh, state.marked)
        state.grew = True
        state.mesh_version += 1


@register_adapt_stage("adapt_mesh", "coarsen_refine")
def _adapt_coarsen_refine(session: "AdaptiveSession", state: SessionState):
    """Time-dependent loop: adapt to the *current* solution before
    stepping -- coarsen first (vertex ids survive append-only, u stays
    valid), then re-estimate on the coarsened mesh and refine.  Leaves
    ``state.eta`` at the post-coarsen indicators (the step's reported
    eta) and records the pre-refine vertex-activity mask for transfer."""
    spec, mesh = session.spec, state.mesh
    estimate = session.stage_fn("estimate")
    state.el = None
    estimate(session, state)
    coarsen(mesh, threshold_coarsen_mark(state.eta, spec.coarsen_frac))
    state.mesh_version += 1     # coarsen+refine can keep n_tets/n_verts
    state.el = None             # constant; the version must still move
    estimate(session, state)
    session.stage_fn("mark")(session, state)
    state.active_before = np.zeros(mesh.n_verts, bool)
    state.active_before[np.unique(mesh.tets)] = True
    state.grew = False
    if mesh.n_tets < spec.max_tets:
        refine(mesh, state.marked)
        state.grew = True
        state.mesh_version += 1


@register_adapt_stage("transfer", "p1")
def _transfer_stage_p1(session: "AdaptiveSession", state: SessionState):
    state.u = transfer_p1(np.asarray(state.u), state.active_before,
                          state.mesh)


def _incremental_keys(session: "AdaptiveSession",
                      state: SessionState) -> np.ndarray:
    """SFC keys for the current mesh with per-step-delta cost.

    Keys live on the leaf payload (``sfc_key``) so refine/coarsen
    propagate them alongside the elements; a copy of each leaf's
    connectivity row at key time (``sfc_tet``) is the dirty signature --
    children and coarsened parents inherit the row of a *different*
    element, so a row mismatch is exactly "this leaf moved".  Only dirty
    leaves re-key, against the session's frozen bounding box, until the
    live box drifts past the cache's tolerance (then everything re-keys
    against a fresh frozen box).  Identical to a full re-key against the
    same frozen box."""
    mesh = state.mesh
    bspec = session.balance_spec
    coords = np.asarray(mesh.barycenters())
    pay = mesh.leaf_payload
    n = mesh.n_tets
    cache = session._key_cache
    dirty = None
    keys = pay.get("sfc_key")
    sig = pay.get("sfc_tet")
    if (cache is not None and keys is not None and len(keys) == n
            and sig is not None and len(sig) == n):
        cache = dataclasses.replace(cache, keys=np.asarray(keys))
        dirty = (np.asarray(sig) != mesh.tets).any(axis=1)
    else:
        cache = None
    cache, info = refresh_key_cache(
        cache, coords, dirty,
        curve="morton" if bspec.method == "msfc" else "hilbert",
        uniform=bspec.method != "hsfc_zoltan", bits=bspec.sfc_bits)
    session._key_cache = cache
    pay["sfc_key"] = cache.keys
    pay["sfc_tet"] = mesh.tets.copy()
    state.key_info = info
    return cache.keys


def _balance_common(session: "AdaptiveSession", state: SessionState):
    """Trigger policy + one DLB step; parts persist in ``leaf_payload``
    so refine/coarsen propagate them to the next step (children inherit).
    """
    spec, mesh = session.spec, state.mesh
    p = session.balance_spec.p
    w = jnp.ones(mesh.n_tets, jnp.float32)
    inherited = mesh.leaf_payload.get("parts")
    if inherited is not None and len(inherited) != mesh.n_tets:
        inherited = None                 # stale payload on a foreign mesh
    # current imbalance of the inherited partition -- only evaluated when
    # a trigger decision or a no-repartition stat needs it (it costs a
    # device reduction + host sync); defined before every use (the old
    # driver left it unbound on the first step)
    cur = float("inf")
    if inherited is not None and spec.trigger != "always":
        cur = float(imbalance(jnp.asarray(inherited), w, p))
    if spec.trigger == "always":
        repart = True
    elif spec.trigger == "never":
        repart = inherited is None       # must partition at least once
    else:                                # 'imbalance' (the paper's)
        repart = inherited is None or cur > spec.imbalance_trigger
    # the owned-layout solve stage may have run this already (step 0 has
    # no partition to pack) -- a later no-repartition decision must not
    # erase that repartition's stats for the step
    first_this_step = state.balanced_step != state.step
    state.balanced_step = state.step
    if repart:
        old = None if inherited is None else jnp.asarray(inherited)
        keys = None
        if spec.incremental and session.balance_spec.method in SFC_METHODS:
            keys = jnp.asarray(_incremental_keys(session, state))
        br = session.balancer.balance(
            w, coords=jnp.asarray(mesh.barycenters()), old_parts=old,
            keys=keys)
        parts = br.parts
        state.balance_result = br
        state.step_imbalance = float(br.imbalance)
        state.migration_totalv = float(br.total_v)
        state.migration_retained = float(br.retained)
        state.repartitioned = True
    else:
        parts = jnp.asarray(inherited)
        state.step_imbalance = cur
        if first_this_step:
            state.balance_result = None
            state.migration_totalv = 0.0
            state.migration_retained = 0.0
            state.repartitioned = False
    mesh.leaf_payload["parts"] = np.asarray(parts)


@register_adapt_stage("balance", "host")
def _balance_host(session: "AdaptiveSession", state: SessionState):
    _balance_common(session, state)


@register_adapt_stage("balance", "sharded")
def _balance_sharded(session: "AdaptiveSession", state: SessionState):
    """Sharded balance: the DLB pipeline runs in one jitted shard_map
    region (via the sharded ``Balancer``), then the mesh's element
    payloads are re-packed across devices with the migration executor's
    ``all_to_all`` -- the paper's per-step data migration, for real.

    With ``vertex_layout='owned'`` the ``HaloPlan`` is rebuilt from the
    fresh partition + connectivity after every repartition (the ghost
    sets change whenever the cut does), connectivity is renumbered to
    part-local slots during the same migration, and the per-matvec
    communication model (replicated psum bytes vs halo bytes vs surface
    index) is recorded for the step's stats."""
    from .parallel import shard_elements_on_device
    _balance_common(session, state)
    if session.spec.vertex_layout == "owned":
        # the solve stage may have packed this very (mesh, partition)
        # already; only a new partition or a mesh mutation needs a repack
        if (state.repartitioned or state.packed_version != state.mesh_version
                or state.sharded is None or state.sharded.layout != "owned"):
            _pack_owned(session, state)
        return
    el = _ensure_elements(state)
    mesh = state.mesh
    state.halo = None
    state.sharded = shard_elements_on_device(
        el, jnp.asarray(mesh.leaf_payload["parts"]),
        session.balance_spec.p, session.device_mesh)
    state.packed_ntets = mesh.n_tets
    state.packed_version = state.mesh_version


# ---------------------------------------------------------------------------
# AdaptiveSession
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _null_scope():
    yield

class AdaptiveSession:
    """Resolve an ``AdaptSpec`` into an executable adaptive loop.

    The session owns loop templates (stationary / time-dependent), calls
    the registered stage functions, centralizes per-stage wall-clock
    timing, emits one ``StepStats`` per step, and invokes user hooks:

    ``on_step(stats, state)``          after each completed step;
    ``on_stage(stage, variant, dt)``   after each top-level stage call.

    ``run(mesh)`` uses the given mesh, else the session's, else the
    problem's registered default mesh factory.
    """

    def __init__(self, spec: AdaptSpec, *, mesh: Optional[Mesh] = None,
                 devices=None, verbose: bool = False,
                 on_step: Optional[Callable] = None,
                 on_stage: Optional[Callable] = None,
                 tracer: Optional["telemetry.Tracer"] = None):
        self.spec = spec
        self.setup = get_problem(spec.problem)
        if self.setup.kind == "parabolic" and spec.stationary:
            raise ValueError(f"problem {spec.problem!r} is time-dependent; "
                             "set dt > 0 and n_steps on the AdaptSpec")
        if self.setup.kind == "stationary" and not spec.stationary:
            raise ValueError(f"problem {spec.problem!r} is stationary; "
                             "dt must be 0 (use max_steps)")
        self.problem = self.setup.make()
        bspec = spec.balance
        if bspec.backend != spec.backend:
            bspec = bspec.replace(backend=spec.backend)
        if spec.incremental and not bspec.warm_start:
            bspec = bspec.replace(warm_start=True)
        self.balance_spec = bspec
        self._key_cache = None          # incremental SFC KeyCache
        # fails fast: sharded backend checks device count / stage variants
        self.balancer = Balancer.from_spec(bspec, devices=devices)
        self.variants = resolve_adapt_variants(spec, self.setup)
        self._stages = {s: get_adapt_stage(s, v)
                        for s, v in self.variants.items() if v is not None}
        self.verbose = verbose
        self.on_step, self.on_stage = on_step, on_stage
        # explicit per-session tracer: run() installs it for the loop's
        # duration; None follows whatever telemetry.tracing() scope is
        # active at run() time
        self.tracer = tracer
        self._mesh = mesh
        self._devices = devices
        self._device_mesh = None

    @property
    def device_mesh(self):
        """Lazily built jax device mesh for the sharded element packing."""
        if self._device_mesh is None:
            from .parallel import device_mesh
            self._device_mesh = device_mesh(self.balance_spec.p,
                                            devices=self._devices)
        return self._device_mesh

    def stage_fn(self, stage: str) -> Callable:
        """The resolved stage function (for nesting inside other stages)."""
        return self._stages[stage]

    # -- timed stage dispatch ----------------------------------------------
    def _run_stage(self, stage: str, state: SessionState,
                   bucket: Optional[str] = None) -> None:
        """Run one registered stage under an always-on stopwatch span.

        The span blocks on the stage's device outputs before the clock
        stops (JAX dispatch is async: without the sync the timing would
        cover enqueueing, not the work), feeds ``state.timings`` /
        ``StepStats``, and lands in the active tracer when telemetry is
        on.  ``on_stage`` stays a thin adapter over the span."""
        fn = self._stages[stage]
        with telemetry.stopwatch(f"adapt/{stage}",
                                 variant=self.variants[stage],
                                 step=state.step) as sw:
            fn(self, state)
            sw.block_on([x for x in (state.u, state.eta,
                                     state.balance_result)
                         if x is not None])
        dt = sw.dur_s
        key = bucket or stage
        state.timings[key] = state.timings.get(key, 0.0) + dt
        if self.on_stage is not None:
            self.on_stage(stage, self.variants[stage], dt)

    # -- loop templates ----------------------------------------------------
    def _step_stationary(self, state: SessionState) -> None:
        with telemetry.span("adapt/elements", step=state.step):
            _ensure_elements(state)
        self._run_stage("solve", state)
        self._run_stage("estimate", state)
        with telemetry.span("adapt/l2_error", step=state.step):
            state.err_l2 = _l2_error(state.el, state.mesh.verts, state.u,
                                     self.problem.exact)
        # mark + refine share the t_refine bucket (as the paper reports)
        self._run_stage("mark", state, bucket="adapt_mesh")
        self._run_stage("adapt_mesh", state)
        self._run_stage("balance", state)

    def _step_timedep(self, state: SessionState) -> None:
        t_next = state.t + self.spec.dt
        self._run_stage("adapt_mesh", state)      # estimate/coarsen/.../refine
        self._run_stage("transfer", state)
        with telemetry.span("adapt/elements", step=state.step):
            _ensure_elements(state)
        self._run_stage("solve", state)
        with telemetry.span("adapt/l2_error", step=state.step):
            state.err_l2 = _l2_error(state.el, state.mesh.verts, state.u,
                                     lambda x: self.problem.exact(x, t_next))
        self._run_stage("balance", state)
        state.t = t_next

    # -- public entry ------------------------------------------------------
    def run(self, mesh: Optional[Mesh] = None) -> AdaptiveResult:
        spec = self.spec
        mesh = mesh if mesh is not None else self._mesh
        if mesh is None:
            mesh = self.setup.default_mesh()
        state = SessionState(mesh=mesh)
        result = AdaptiveResult(spec=spec)
        stationary = self.setup.kind == "stationary"
        if not stationary:
            # initial condition: interpolate exact at t = 0
            state.u = np.asarray(self.problem.exact(jnp.asarray(mesh.verts),
                                                    0.0))
        n_iters = spec.max_steps if stationary else spec.n_steps
        scope = (telemetry.tracing(self.tracer) if self.tracer is not None
                 else _null_scope())
        with scope:
            self._run_steps(state, result, stationary, n_iters)
        if state.u is not None:
            result.u = jnp.asarray(state.u)
        result.mesh = state.mesh
        result.sharded = state.sharded
        result.halo = state.halo
        return result

    def _run_steps(self, state: SessionState, result: AdaptiveResult,
                   stationary: bool, n_iters: int) -> None:
        tr = telemetry.get_tracer()
        for step in range(n_iters):
            state.step = step
            state.timings = {}
            with tr.span("adapt/step", step=step) as sp:
                if stationary:
                    self._step_stationary(state)
                else:
                    self._step_timedep(state)
                sp.set(n_tets=state.mesh.n_tets)
            with tr.span("adapt/emit_stats", step=step):
                stats = self._emit_stats(state)
            result.stats.append(stats)
            tr.tick(step)
            if state.repartitioned:
                result.n_repartitions += 1
            if self.on_step is not None:
                self.on_step(stats, state)
            if self.verbose:
                head = (f"[{step}]" if stationary else f"[t={state.t:.3f}]")
                print(f"{head} nt={stats.n_tets:7d} err={stats.err_l2:.3e} "
                      f"eta={stats.eta:.3e} cg={stats.cg_iters} "
                      f"imb={stats.imbalance:.3f} "
                      f"solve={stats.t_solve:.2f}s "
                      f"bal={stats.t_balance:.3f}s")
            if stationary and not state.grew:
                break

    def _emit_stats(self, state: SessionState) -> StepStats:
        tr = telemetry.get_tracer()
        if tr.enabled:
            if state.cut is None:
                # only the owned-sharded packing computes the cut on its
                # own; under tracing, pay for it on every backend so the
                # quality counters are backend-independent
                parts = state.mesh.leaf_payload.get("parts")
                if parts is not None and len(parts) == state.mesh.n_tets:
                    state.cut = int(cut_links(
                        jnp.asarray(parts),
                        jnp.asarray(state.mesh.face_adjacency())))
            if state.cut is not None:
                tr.metrics.gauge(
                    "cut", unit="links",
                    help="element-adjacency links crossing parts "
                         "(paper surface index)").set(int(state.cut))
        eta2 = np.asarray(state.eta, np.float64) ** 2
        tm = state.timings
        return StepStats(
            n_tets=state.mesh.n_tets, n_verts=state.mesh.n_verts,
            eta=float(np.sqrt(eta2.sum())), err_l2=state.err_l2,
            cg_iters=state.cg_iters,
            t_solve=tm.get("solve", 0.0),
            t_estimate=tm.get("estimate", 0.0),
            t_refine=tm.get("adapt_mesh", 0.0),
            t_balance=tm.get("balance", 0.0),
            imbalance=state.step_imbalance,
            repartitioned=state.repartitioned,
            migration_totalv=state.migration_totalv,
            cut=state.cut,
            migration_retained=state.migration_retained,
            t_transfer=tm.get("transfer", 0.0),
            comm_psum_bytes=state.comm_psum_bytes,
            comm_halo_bytes=state.comm_halo_bytes)


# ---------------------------------------------------------------------------
# Deprecated driver wrappers
# ---------------------------------------------------------------------------

# one shared key for both legacy drivers: the old machinery warned once
# per process across the pair, not once per driver
_DEPRECATION_KEY = "fem.adapt.legacy_drivers"


def _warn_deprecated_once(name: str) -> None:
    """Emit the legacy-driver DeprecationWarning once per process."""
    deprecation.warn_once(
        _DEPRECATION_KEY,
        f"{name} is deprecated; build an AdaptSpec and use "
        "repro.fem.AdaptiveSession(spec).run(mesh) instead")


def _reset_deprecation_warning() -> None:
    """Testing hook: allow the once-per-process warning to fire again."""
    deprecation.reset(_DEPRECATION_KEY)


def solve_helmholtz_adaptive(mesh: Mesh, *, p: int = 16,
                             method: str = "hsfc",
                             theta: float = 0.5,
                             max_steps: int = 10,
                             max_tets: int = 200_000,
                             imbalance_trigger: float = 1.05,
                             tol: float = 1e-8,
                             backend: str = "host",
                             verbose: bool = False) -> AdaptiveResult:
    """DEPRECATED -- paper Example 3.1 via ``AdaptiveSession``.

    Equivalent to ``AdaptiveSession(AdaptSpec(problem='helmholtz', ...))
    .run(mesh)``; kwargs map 1:1 onto spec fields (see ROADMAP's
    migration guide)."""
    _warn_deprecated_once("solve_helmholtz_adaptive")
    spec = AdaptSpec(problem="helmholtz", theta=theta, trigger="imbalance",
                     imbalance_trigger=imbalance_trigger,
                     balance=BalanceSpec(p=p, method=method, backend=backend),
                     backend=backend, max_steps=max_steps, max_tets=max_tets,
                     tol=tol)
    return AdaptiveSession(spec, verbose=verbose).run(mesh)


def solve_parabolic_adaptive(mesh: Mesh, *, p: int = 16,
                             method: str = "hsfc", dt: float = 0.01,
                             n_steps: int = 20, theta: float = 0.4,
                             max_tets: int = 120_000,
                             coarsen_frac: float = 0.15,
                             tol: float = 1e-8,
                             backend: str = "host",
                             verbose: bool = False) -> AdaptiveResult:
    """DEPRECATED -- paper Example 3.2 via ``AdaptiveSession``.

    Unlike the old driver, the previous step's partition is threaded into
    every balance call, so the Oliker--Biswas remap and the migration
    metrics (``retained`` > 0 after the first step) are live."""
    _warn_deprecated_once("solve_parabolic_adaptive")
    spec = AdaptSpec(problem="parabolic", theta=theta,
                     coarsen_frac=coarsen_frac, trigger="always",
                     balance=BalanceSpec(p=p, method=method, backend=backend),
                     backend=backend, dt=dt, n_steps=n_steps,
                     max_tets=max_tets, tol=tol)
    return AdaptiveSession(spec, verbose=verbose).run(mesh)


# ---------------------------------------------------------------------------
# Solution transfer
# ---------------------------------------------------------------------------

def peak_init(mesh: Mesh, prob: ParabolicProblem) -> jax.Array:
    return prob.exact(jnp.asarray(mesh.verts), 0.0)


def transfer_p1(u_old: np.ndarray, active_before: np.ndarray,
                mesh: Mesh) -> np.ndarray:
    """Transfer nodal values to the adapted mesh.

    ``active_before`` is the bool mask of vertices referenced by leaves
    before refinement (length may be < current n_verts).  Values there are
    kept; every other vertex now in use is a bisection midpoint whose value
    is the mean of its edge endpoints (exact P1 interpolation).  A midpoint
    always has a larger vertex id than its endpoints, so one forward pass
    in id order resolves chains."""
    old_nv = active_before.shape[0]
    u_new = np.zeros(mesh.n_verts, np.float64)
    u_new[:old_nv] = np.asarray(u_old)[:old_nv]
    needs = np.ones(mesh.n_verts, bool)
    needs[:old_nv] = ~active_before
    if needs.any():
        pairs = np.array([[k >> 32, k & 0xFFFFFFFF, v]
                          for k, v in mesh.edge_mid.items()
                          if needs[v]], np.int64)
        if pairs.size:
            order = np.argsort(pairs[:, 2])
            for a, b, v in pairs[order]:
                u_new[v] = 0.5 * (u_new[a] + u_new[b])
    return u_new
