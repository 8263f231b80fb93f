"""Round programs: the shared skeleton of every federated aggregation round.

The paper's Algorithms 1–6 (FeDLRT full/simplified, FedAvg, FedLin, naive
per-client low-rank) all instantiate the same four-phase round::

    broadcast   server-side prep at the shared point: global gradients,
                basis augmentation, per-client correction terms
    client_step one client's local work (vmapped over the cohort axis by
                the runner — jit/GSPMD friendly, no host loop)
    aggregate   server reduction over the cohort (weighted mean → under a
                sharded client axis this lowers to the paper's all-reduce)
    finalize    truncation / metric assembly on the aggregated state

:func:`run_round` executes any :class:`RoundProgram` through that skeleton.
The phases communicate through plain pytrees; everything cohort-shaped
carries a leading client axis ``C`` (the *active cohort*, which under
partial participation is smaller than the population — see
:mod:`repro.fed.participation`).

The phase boundaries are also the round's *data plane*: what ``broadcast``
hands the clients crosses the wire down, what ``client_step`` returns
crosses up.  :func:`run_round` optionally threads those payloads through a
:class:`repro.fed.wire.Wire` (owned by the engine) — encode/decode plus
measured byte accounting — while server-local state stays out of the
transmission via the ``shared[SERVER]`` convention (see :data:`SERVER`).

Shared building blocks that used to be duplicated per algorithm live here:
:func:`local_sgd_scan` (the s*-step client loop as one ``lax.scan``) and
:func:`variance_correction` (the FedLin/FeDLRT control-variate term).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.optim import make_optimizer
from repro.utils.tree import tree_mean_leading_axis

Array = jax.Array
LossFn = Callable[[Any, Any], Array]  # (params, batch) -> scalar


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Hyperparameters of one federated optimization run.

    ``num_clients`` is the size of the *active cohort* a round function
    sees — with partial participation the engine rebuilds the config per
    cohort size (jit caches one executable per size).
    """

    num_clients: int
    s_star: int  # local iterations per round
    lr: float = 1e-3
    correction: str = "simplified"  # "none" | "simplified" | "full"
    tau: float = 0.01  # relative singular-value truncation threshold
    optimizer: str = "sgd"
    momentum: float = 0.0
    per_step_batches: bool = False  # batch leaves have a (C, s*, ...) layout
    eval_after: bool = True  # compute global loss after the round (extra fwd)
    track_drift: bool = False  # record max_s ‖S̃_c^s − S̃‖ (Theorem-1 diagnostics)
    # replicate the augmented bases for the client loop (hypothesis Q3 in
    # EXPERIMENTS.md §Perf: gather-once beats per-step gathers).  REFUTED on
    # qwen2 train_4k — XLA already hoists the per-step gathers out of the
    # scan, so forced replication only added resharding traffic (+75% on
    # the collective term) and +4.5 GiB temp.  Kept as a switch.
    replicate_augmented: bool = False

    def __post_init__(self):
        if self.correction not in ("none", "simplified", "full"):
            raise ValueError(
                f"correction must be 'none', 'simplified' or 'full', "
                f"got {self.correction!r}"
            )
        if self.num_clients <= 0:
            raise ValueError(
                f"num_clients must be a positive cohort size, got {self.num_clients}"
            )
        if self.s_star <= 0:
            raise ValueError(
                f"s_star (local iterations per round) must be positive, "
                f"got {self.s_star}"
            )
        if self.lr <= 0:
            raise ValueError(f"lr must be positive, got {self.lr}")
        if not 0.0 <= self.tau < 1.0:
            raise ValueError(
                f"tau is a *relative* singular-value threshold and must lie "
                f"in [0, 1), got {self.tau}"
            )


@dataclasses.dataclass(frozen=True)
class RoundContext:
    """Everything a phase needs beyond its own pytrees.

    ``aggregate`` reduces a leading-client-axis pytree to the server value
    (plain or ``client_weights``-weighted mean); ``vmap_c`` is the client
    vmap, carrying ``spmd_axis_name`` when the client axis lives on mesh
    axes.  Both are closures so programs stay oblivious to weighting and
    sharding concerns.
    """

    cfg: FedConfig
    round_idx: Array
    aggregate: Callable[[Any], Any]
    vmap_c: Callable
    client_weights: Optional[Array] = None
    spec_tree: Any = None
    client_axes: Any = None


#: key under which ``broadcast`` stashes server-local state.  Everything
#: else in the shared dict is *downlink payload* — it crosses the wire to
#: every client (and is what a :class:`repro.fed.wire.Wire` encodes).
#: ``client_step`` never sees the server entry; ``aggregate``/``finalize``
#: get the full original shared dict (the server keeps its own copies).
SERVER = "__server__"

#: canonical phase names of a federated round, in execution order.  The
#: telemetry span names the engines emit (``phase.client_step``,
#: ``phase.aggregate``, …) are ``"phase." + <one of these>`` — keep them
#: in sync so traces stay greppable against the RoundProgram protocol.
PHASES = ("broadcast", "client_step", "aggregate", "finalize")


def split_server(shared):
    """Split a broadcast ``shared`` dict into ``(downlink, server_state)``.

    Programs that predate the wire layer (plain dicts without a
    :data:`SERVER` entry) broadcast everything.
    """
    if isinstance(shared, dict) and SERVER in shared:
        return {k: v for k, v in shared.items() if k != SERVER}, shared[SERVER]
    return shared, None


@runtime_checkable
class RoundProgram(Protocol):
    """One federated algorithm, decomposed into the four round phases."""

    def broadcast(self, loss_fn: LossFn, params, client_batches, ctx: RoundContext):
        """Server-side prep.  Returns ``(shared, per_client)`` where
        ``shared`` is broadcast state closed over by every client and
        ``per_client`` carries a leading client axis (or is None).

        Wire contract: ``shared`` entries are *transmitted* to every
        client; values only the server needs (metrics, cached gradients)
        belong under ``shared[SERVER]`` so they are neither measured nor
        degraded by a lossy wire codec.  ``per_client`` is sliced along its
        leading axis — client ``c`` receives (and is billed for) row ``c``.
        """
        ...

    def client_step(self, loss_fn: LossFn, shared, per_client, batches, ctx: RoundContext):
        """One client's local work (the runner vmaps this over the cohort).

        ``shared``/``per_client`` here are the *received* payloads: the
        :data:`SERVER` entry is stripped, and under a lossy wire codec the
        tensors carry that codec's on-wire representation error.
        """
        ...

    def aggregate(self, shared, client_out, ctx: RoundContext):
        """Server reduction over the stacked client outputs.  ``shared`` is
        the original broadcast dict (server-side copies); ``client_out`` is
        what arrived back over the wire."""
        ...

    def finalize(self, loss_fn: LossFn, params, shared, agg, client_batches, ctx: RoundContext):
        """Post-aggregation server work.  Returns ``(new_params, metrics)``."""
        ...


def make_aggregator(client_weights: Optional[Array]) -> Callable[[Any], Any]:
    """Leading-axis reduction: plain mean, or normalized ``w``-weighted mean
    (the paper's §2 non-uniform |X_c| extension)."""
    if client_weights is None:
        return tree_mean_leading_axis
    w = jnp.asarray(client_weights, jnp.float32)
    w = w / jnp.sum(w)

    def aggregate(tree):
        return jax.tree.map(
            lambda x: jnp.tensordot(
                w.astype(jnp.float32), x.astype(jnp.float32), axes=1
            ).astype(x.dtype),
            tree,
        )

    return aggregate


def make_context(
    cfg: FedConfig,
    *,
    round_idx: Array | int = 0,
    client_weights: Optional[Array] = None,
    spec_tree=None,
    client_axes=None,
) -> RoundContext:
    vmap_c = (
        functools.partial(jax.vmap, spmd_axis_name=client_axes)
        if client_axes
        else jax.vmap
    )
    return RoundContext(
        cfg=cfg,
        round_idx=jnp.asarray(round_idx),
        aggregate=make_aggregator(client_weights),
        vmap_c=vmap_c,
        client_weights=client_weights,
        spec_tree=spec_tree,
        client_axes=client_axes,
    )


def run_client_phases(
    program: RoundProgram,
    loss_fn: LossFn,
    params,
    client_batches,
    ctx: RoundContext,
    *,
    wire=None,
):
    """The data-plane half of a round: ``broadcast`` then the vmapped
    ``client_step``, with every boundary payload threaded through ``wire``.

    Returns ``(shared, client_out, (bytes_shared, bytes_per_client,
    bytes_up))`` — the server-side broadcast dict (with its ``SERVER``
    entry intact), the stacked client outputs *as received over the wire*,
    and the measured byte totals per payload.  :func:`run_round` is this
    followed by ``aggregate``/``finalize``; the async simulation engine
    (:mod:`repro.fed.sim`) calls it directly to run departure-anchored
    client work for one staleness group at a time.
    """
    shared, per_client = program.broadcast(loss_fn, params, client_batches, ctx)
    # clients only ever see the downlink part; the server keeps `shared`
    client_shared, _ = split_server(shared)
    bytes_shared = bytes_pc = bytes_up = 0
    if wire is not None:
        client_shared, bytes_shared = wire.roundtrip(client_shared, name="broadcast")
        per_client, bytes_pc = wire.roundtrip(
            per_client, name="per_client", batched=True
        )
    client_out = ctx.vmap_c(
        lambda pc, b: program.client_step(loss_fn, client_shared, pc, b, ctx),
        in_axes=(0, 0),
    )(per_client, client_batches)
    if wire is not None:
        client_out, bytes_up = wire.roundtrip(
            client_out, name="client_out", batched=True
        )
    return shared, client_out, (bytes_shared, bytes_pc, bytes_up)


def run_round(
    program: RoundProgram,
    loss_fn: LossFn,
    params,
    client_batches,
    cfg: FedConfig,
    *,
    round_idx: Array | int = 0,
    client_weights: Optional[Array] = None,
    spec_tree=None,
    client_axes=None,
    wire=None,
):
    """Execute one round of ``program``.  Returns ``(new_params, metrics)``.

    ``wire`` (optional :class:`repro.fed.wire.Wire`) decorates the phase
    boundaries — the data plane of the round: the broadcast downlink and
    per-client slices are encoded/decoded before ``client_step`` sees them,
    the client outputs before ``aggregate`` sees them.  Measured bytes land
    in the metrics as ``wire_bytes_down_per_client`` /
    ``wire_bytes_up_per_client`` (down counts the shared broadcast once per
    client plus that client's slice).  Programs need no changes: with the
    identity codec the round is bit-identical to ``wire=None``.
    """
    ctx = make_context(
        cfg,
        round_idx=round_idx,
        client_weights=client_weights,
        spec_tree=spec_tree,
        client_axes=client_axes,
    )
    shared, client_out, (bytes_shared, bytes_pc, bytes_up) = run_client_phases(
        program, loss_fn, params, client_batches, ctx, wire=wire
    )
    agg = program.aggregate(shared, client_out, ctx)
    new_params, metrics = program.finalize(
        loss_fn, params, shared, agg, client_batches, ctx
    )
    if wire is not None:
        metrics = dict(metrics)
        metrics["wire_bytes_down_per_client"] = _per_client_bytes(
            bytes_shared, bytes_pc, cfg.num_clients
        )
        metrics["wire_bytes_up_per_client"] = _per_client_bytes(
            0, bytes_up, cfg.num_clients
        )
    return new_params, metrics


def _per_client_bytes(shared_bytes, batched_bytes, num_clients: int):
    """``shared + batched/C`` per-client bytes, exactly when possible.

    Static codec counts are python ints whose batched totals divide evenly
    over the ``C`` equal-size client slices — integer arithmetic keeps the
    measured == analytic contract exact up to int32 range (~2 GiB/client/
    direction) instead of f32's 2^24 bytes.  Beyond int32 (a 7B model's
    identity-codec payload) the count leaves jit as f32, like the analytic
    figure.  Traced counts (topk_rank's rank-dependent meter) take the f32
    path.
    """
    if (
        isinstance(shared_bytes, int)
        and isinstance(batched_bytes, int)
        and batched_bytes % num_clients == 0
    ):
        total = shared_bytes + batched_bytes // num_clients
        return total if total < 2**31 else float(total)
    return jnp.float32(shared_bytes) + jnp.float32(batched_bytes) / num_clients


# ---------------------------------------------------------------------------
# shared building blocks
# ---------------------------------------------------------------------------


def first_step_batch(client_batches, cfg: FedConfig):
    """The cohort's step-0 batch: ``x[:, 0]`` under per-step layout."""
    if cfg.per_step_batches:
        return jax.tree.map(lambda x: x[:, 0], client_batches)
    return client_batches


def last_step_batch(client_batches, cfg: FedConfig):
    if cfg.per_step_batches:
        return jax.tree.map(lambda x: x[:, -1], client_batches)
    return client_batches


def select_step_batch(batches, s: Array, cfg: FedConfig):
    """One client's batch for local step ``s`` (inside the vmapped scan)."""
    if cfg.per_step_batches:
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, s, 0, keepdims=False), batches
        )
    return batches


def variance_correction(g_global, g_clients):
    """Control-variate term ``corr_c = ḡ − g_c`` (paper Eq. (4) / Eq. (8)).

    Enters each local step as ``∇L_c(w) + corr_c`` so the expected client
    update follows the *global* gradient; with a plain-mean aggregate the
    corrections sum to zero over the cohort.
    """
    return jax.tree.map(
        lambda gbar, gc: jnp.broadcast_to(gbar, gc.shape) - gc, g_global, g_clients
    )


def local_sgd_scan(
    loss_fn: LossFn,
    params0,
    corr,
    batches,
    cfg: FedConfig,
    *,
    transform_grads: Optional[Callable[[Any], Any]] = None,
    project: Optional[Callable[[Any], Any]] = None,
    drift_fn: Optional[Callable[[Any], Array]] = None,
):
    """One client's s* local (optionally corrected) SGD steps as a scan.

    The single implementation behind every round program's client loop:
    FeDLRT passes ``transform_grads``/``project`` to keep coefficient
    updates in the 2r active directions, the dense baselines use it bare.
    ``corr=None`` means uncorrected (no control variate is added — and, under
    the wire layer, none is transmitted).  ``drift_fn`` (optional)
    accumulates ``max_s drift_fn(params_s)`` — the Theorem-1 diagnostic.
    Returns ``(params_s*, max_drift)``.
    """
    opt = make_optimizer(cfg.optimizer, cfg.lr, momentum=cfg.momentum)
    state0 = opt.init(params0)

    def step(carry, s):
        p, ost, drift = carry
        b = select_step_batch(batches, s, cfg)
        g = jax.grad(loss_fn)(p, b)
        if corr is not None:
            g = jax.tree.map(jnp.add, g, corr)
        if transform_grads is not None:
            g = transform_grads(g)
        upd, ost = opt.update(g, ost, s)
        # cast: f32 lr × bf16 grad promotes; carry dtype must be stable
        p = jax.tree.map(lambda t, u: t + u.astype(t.dtype), p, upd)
        if project is not None:
            p = project(p)
        if drift_fn is not None:
            drift = jnp.maximum(drift, drift_fn(p))
        return (p, ost, drift), ()

    (p, _, drift), _ = jax.lax.scan(
        step, (params0, state0, jnp.zeros(())), jnp.arange(cfg.s_star)
    )
    return p, drift
