"""Shared local-training engine for every FL algorithm.

One jitted SGD step per loss variant (plain / prox / moon); all algorithms
reuse these, so accuracy differences between algorithms come from the
*aggregation schedule*, never from divergent local implementations. Momentum
is reset at the start of each client visit (the model hops between devices;
optimizer state does not travel with it).

The execution engines (``core.engines``) share the same losses and update
rule through three entry points:

* ``train`` — a python loop over single-client jitted steps (the reference
  semantics, one dispatch per batch). Consumes a pre-drawn batch plan or
  draws one itself; per-step host->device batch bytes are metered into
  ``h2d_bytes`` so all four engines compare on one axis.
* ``train_many`` — every concurrent client visit of a round runs at once.
  Model/momentum pytrees are stacked along a leading client axis, the
  per-client gradient is ``jax.vmap``-ed, and a ``jax.lax.scan`` walks the
  padded step axis; a (C, S) valid mask turns padded steps into no-ops for
  the clients that ran out of data, so uneven shard sizes batch cleanly.
  Cohort-shared extras (FedProx's anchor, MOON's global model, SCAFFOLD's
  server control variate) are passed as ONE tree and broadcast inside the
  jit (``vmap in_axes=None`` / elementwise broadcasting) — the host never
  materializes C copies; per-client extras (MOON's previous locals,
  SCAFFOLD's client variates) stay client-stacked. With ``mesh``, every
  C-stacked input is placed on a ``jax.sharding.Mesh`` data axis via
  ``NamedSharding`` (the sharded engine); C must be a multiple of the mesh
  axis (callers ghost-pad).
* ``train_many_fused`` — the batched math against a device-resident
  ``DeviceDataPlane``. Per call, only int32 plan arrays cross H2D; the
  scan body gathers each step's batch of feature rows from the resident
  fleet stack and reshapes it to the model's input. A leading hop axis H
  runs as an OUTER ``lax.scan`` carrying the model stack, so a whole ring
  lap sequence (R*K visits) is ONE compiled dispatch; the non-broadcast
  family donates the params stack to the computation (in-place update on
  accelerator backends).
* ``train_schedule`` — one level further: a whole eval-to-eval BLOCK of
  rounds as one compiled call. A ``lax.scan`` over the round axis carries
  ``(w_glob, algo_state)`` — each round body broadcasts the carried
  global, reruns the fused hop scan, contracts the round's aggregation
  vector and updates the device-resident algorithm state (``core.state``)
  in place. Per-round lr ships as one (n,) device array; HierFAVG's R
  chained edge iterations run as an inner scan with the per-edge reduce
  in the body.

**In-jit aggregation** (``agg=``): both stacked entry points accept the
reduction array of an ``AggSpec`` (see ``core.plan``) and contract it
against the trained lane stack *inside the same compiled call* — a (C,)
vector collapses the round to ONE aggregated model, a (G, C) matrix
reduces lanes to their per-edge group models. The round's weighted cloud
reduce (eq. 11) therefore never bounces C model trees through the host,
and the fused FedSR round — broadcast, H-hop ring scan, weighted cloud
reduce — is a single dispatch (``dispatches`` counts them).
``keep_locals=True`` additionally returns the per-lane trained stack
(MOON/SCAFFOLD state updates read it).

The update rule itself is elementwise, so one implementation serves every
engine — and can optionally run as a single fused Pallas pass over the
raveled parameter vector (``FLConfig.use_fused_sgd``).

Both fused entry points are store-agnostic (``FLConfig.store``): the
``DeviceDataPlane`` they gather from may hold the whole fleet or only a
block's visited cohort (``data.store.HostStore``) — the plane's offsets
table is fleet-sized either way, so the traced gather's addressing
never changes; only the array the offsets point into does.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import FLConfig, ModelConfig
from repro.core.robust import robust_agg
from repro.data.pipeline import plan_epoch_indices
from repro.models.small import classifier_loss, small_model_features
from repro.utils import trace
from repro.utils.tree import tree_sq_norm, tree_sub

Pytree = Any

# the default (exact eq.-11) reduce spec: (reducer, trim_frac, krum_f)
_WMEAN = ("weighted_mean", 0.0, 0)


def _scoped(name: str):
    """Trace the function under ``jax.named_scope(name)``: the ops it
    emits carry ``name`` in their HLO ``op_name`` metadata (profiles,
    dumps). The computation is unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


_robust_agg = _scoped("edge_cloud_reduce")(robust_agg)


def _expand_mask(ok, x):
    """Broadcast a (C,) per-client step mask against a (C, ...) leaf."""
    return ok.reshape(ok.shape + (1,) * (x.ndim - 1))


def _h2d_nbytes(a) -> int:
    """Bytes that actually cross H2D for one host array: jax demotes 64-bit
    dtypes to 32-bit on transfer while x64 is disabled, so int64 label
    stacks ship as int32 — count those, not the host representation."""
    a = np.asarray(a)
    return a.size * min(a.dtype.itemsize, 4)


def _put(tree, sharding):
    """Place every leaf straight onto ``sharding``: host arrays go to each
    device's shard directly, never through one committed device first."""
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def _donation_supported() -> bool:
    """Buffer donation is a no-op (with a warning) on the CPU backend; only
    request it where XLA can actually alias the update in place."""
    return jax.default_backend() != "cpu"


@_scoped("edge_cloud_reduce")
def _tree_agg(stack, w):
    """Contract the reduction array against a (C, ...) lane stack: a (C,)
    vector yields the single aggregated tree, a (G, C) matrix the (G, ...)
    per-group stack — ONE tensordot per leaf, inside the jit."""
    return jax.tree.map(
        lambda x: jnp.tensordot(w.astype(x.dtype), x, axes=[[-1], [0]]),
        stack)


def _tree_bcast(tree, n: int):
    """Stack ``n`` copies of a tree along a new leading axis, in-jit."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def _apply_lane_scale(stack, scale, ref):
    """The adversary's in-jit Byzantine delta transform: lane c's trained
    model becomes ``ref + scale[c] * (model - ref)`` (``core.adversary``
    stamps ``scale`` on the plan; honest lanes carry 1.0). ``ref`` is the
    lane seed — a single tree (broadcasts against the (C, ...) stack) or a
    (C, ...) stacked tree of per-lane seeds."""
    return jax.tree.map(
        lambda p, r: r + _expand_mask(scale, p) * (p - r), stack, ref)


def _reduce_stack(stack, aggm, gw, rspec):
    """Contract the reduce over the trained lane stack, in-jit: the exact
    eq.-11 tensordot (``weighted_mean``, bit-for-bit the historic path) or
    a Byzantine-robust order statistic (``core.robust``)."""
    if rspec[0] == "weighted_mean":
        return _tree_agg(stack, aggm)
    return _robust_agg(stack, aggm, gw, rspec[0], rspec[1], rspec[2])


def _split_head(rest, dp: bool, mode: str, has_gw: bool, has_dscale: bool,
                has_dref: bool):
    """Unpack the static head of a many()/fused ``*rest``: optional DP key,
    then (for reducing modes) ``aggm [, gw][, dscale][, dref]``, then the
    variant's loss/update extras. Presence flags are static, so the
    default path's jaxpr is unchanged."""
    i = 0
    key = aggm = gw = ds = dref = None
    if dp:
        key = rest[0]
        i = 1
    if mode != "stack":
        aggm = rest[i]
        i += 1
        if has_gw:
            gw = rest[i]
            i += 1
        if has_dscale:
            ds = rest[i]
            i += 1
        if has_dref:
            dref = rest[i]
            i += 1
    return key, aggm, gw, ds, dref, rest[i:]


def _make_dp(clip: float, sigma: float, stacked: bool):
    """DP-SGD per-gradient transform: clip to L2 norm ``clip`` (per lane
    when ``stacked``), then add N(0, sigma^2) noise (sigma already folded
    as ``dp_noise_mult * dp_clip``). One fresh key per call; noise is
    independent per leaf and per lane."""
    def apply(grads, key):
        leaves, treedef = jax.tree.flatten(grads)
        if stacked:
            sq = sum(jnp.sum(leaf * leaf, axis=tuple(range(1, leaf.ndim)))
                     for leaf in leaves)
        else:
            sq = sum(jnp.sum(leaf * leaf) for leaf in leaves)
        fac = jnp.minimum(1.0, clip / jnp.sqrt(sq + 1e-12))
        keys = jax.random.split(key, len(leaves))
        out = []
        for leaf, k in zip(leaves, keys):
            f = _expand_mask(fac, leaf) if stacked else fac
            leaf = leaf * f
            if sigma > 0:
                leaf = leaf + sigma * jax.random.normal(k, leaf.shape,
                                                        leaf.dtype)
            out.append(leaf)
        return jax.tree.unflatten(treedef, out)
    return apply


@_scoped("hop_gather")
def hop_gather(images, labels, offsets, row_s, ix, item_shape):
    """One hop step's batch from a ``DeviceDataPlane``: lane ``c`` reads
    fleet row ``row_s[c]``'s samples ``ix[c]`` (C, B) as images
    ``(C, B) + item_shape`` and labels (C, B).

    Fleet row r, sample i -> flat row ``offsets[r] + i``: ONE (C, B)-indexed
    gather per array, so a step reads C*B rows — a per-lane take-of-take
    would materialize (C, N_max, ...) intermediates and all-gather the
    sharded plane instead. Plans index a client's own ``[0, len)`` and
    ghost/unvisited lanes map to row 0, so every index is in bounds: a
    bare gather of contiguous feature rows, with no out-of-bounds fill
    select fused over the batch (``tests/test_hop_gather.py`` holds the
    engines to that bound)."""
    def rows_at(a, i):
        return a.at[i].get(mode="promise_in_bounds")
    gidx = rows_at(offsets, row_s)[:, None] + ix
    x = rows_at(images, gidx)
    return {"images": x.reshape(x.shape[:2] + item_shape),
            "labels": rows_at(labels, gidx)}


def _run_hops(vgrad, update, n_loss_extras, params, images, labels, offsets,
              rows, plans, valid, lr, extras, item_shape, dp=None, key=None):
    """The flat H*S-step gathered-SGD scan over one visit group, shared by
    ``train_many_fused`` and the schedule dispatch (``train_schedule``).

    ``params`` is the already-stacked (C, ...) lane stack; ``rows`` (H, C),
    ``plans`` (H, C, S, B) and ``valid`` (H, C, S) index the device-resident
    fleet arrays. The (hop, step) axes flatten into ONE scan: a nested
    scan-in-scan pays per-hop setup (inner scan machinery, fresh zero
    momentum buffers) every hop, which dominates in the dispatch-bound S=1
    regime. Instead the momentum carry is zeroed by a per-step reset flag
    wherever a new client visit begins — same math, one flat scan of H*S
    gathered SGD steps. Returns the trained (C, ...) stack.

    ``images`` is the plane's flat (total, D) feature-row array and
    ``item_shape`` the model's static per-sample input shape; each step's
    batch comes from ``hop_gather``.

    ``dp``/``key`` opt the scan into DP-SGD: the per-step gradient passes
    through the ``_make_dp`` transform with a key split from the scan
    carry (dp-off builds today's scan body, bit-for-bit)."""
    H, _, S = valid.shape
    flat_rows = jnp.repeat(rows, S, axis=0)
    flat_ix = jnp.transpose(plans, (0, 2, 1, 3)).reshape(
        (H * S,) + plans.shape[1:2] + plans.shape[3:])
    flat_ok = jnp.transpose(valid, (0, 2, 1)).reshape(
        H * S, -1).astype(jnp.float32)
    reset = (jnp.arange(H * S) % S == 0).astype(jnp.float32)
    m = jax.tree.map(jnp.zeros_like, params)
    xs = (flat_rows, flat_ix, flat_ok, reset)

    def gather(row_s, ix):
        return hop_gather(images, labels, offsets, row_s, ix, item_shape)

    if dp is None:
        def body(carry, x):
            pc, mc = carry
            row_s, ix, ok, rs = x   # (C,), (C, B), (C,), scalar
            mc = jax.tree.map(lambda mi: (1.0 - rs) * mi, mc)
            g = vgrad(pc, gather(row_s, ix), *extras[:n_loss_extras])
            return update(pc, mc, g, lr,
                          *extras[n_loss_extras:], ok), None

        (p, _), _ = jax.lax.scan(body, (params, m), xs)
    else:
        def body(carry, x):
            pc, mc, kc = carry
            row_s, ix, ok, rs = x
            kc, sub = jax.random.split(kc)
            mc = jax.tree.map(lambda mi: (1.0 - rs) * mi, mc)
            g = vgrad(pc, gather(row_s, ix), *extras[:n_loss_extras])
            g = dp(g, sub)
            return update(pc, mc, g, lr,
                          *extras[n_loss_extras:], ok) + (kc,), None

        (p, _, _), _ = jax.lax.scan(body, (params, m, key), xs)
    return p


class LocalTrainer:
    """Builds and caches the jitted local steps for one (model, FL) config."""

    def __init__(self, cfg: ModelConfig, fl: FLConfig,
                 grad_mask: Optional[Pytree] = None):
        self.cfg = cfg
        self.fl = fl
        # one sample's input shape: the fused engines reshape each step's
        # gathered (C, B, D) feature rows from the data plane to it
        self._item_shape = (cfg.image_size, cfg.image_size,
                            cfg.image_channels)

        # ``grad_mask`` freezes parameter subtrees at construction (like
        # DP-SGD, baked so mask-off builds literally today's jaxpr): a
        # params-shaped 0/1 pytree multiplied into every gradient before
        # the update. Zeroed leaves never move (zero grads leave momentum
        # at zero too) — the head-only personalization mode
        # (``PersonalizeConfig.mode="head"``) trains just the classifier
        # layer this way, through every engine path unchanged.
        if grad_mask is not None:
            _mask = jax.tree.map(
                lambda mk: jnp.asarray(mk, jnp.float32), grad_mask)

            def _grad(loss_fn):
                raw = jax.grad(loss_fn)

                def masked(params, *args):
                    return jax.tree.map(lambda g, mk: g * mk,
                                        raw(params, *args), _mask)
                return masked
        else:
            def _grad(loss_fn):
                return jax.grad(loss_fn)
        self._grad = _grad

        def plain_loss(params, batch):
            return classifier_loss(params, batch, cfg)

        def prox_loss(params, batch, anchor):
            # FedProx: + mu/2 ||w - w_glob||^2
            prox = 0.5 * fl.mu * tree_sq_norm(tree_sub(params, anchor))
            return classifier_loss(params, batch, cfg) + prox

        def moon_loss(params, batch, w_glob, w_prev):
            # MOON: model-contrastive loss against global (positive) and
            # previous-local (negative) representations.
            z = small_model_features(params, batch["images"], cfg)
            z_g = jax.lax.stop_gradient(
                small_model_features(w_glob, batch["images"], cfg))
            z_p = jax.lax.stop_gradient(
                small_model_features(w_prev, batch["images"], cfg))

            def cos(a, b):
                a = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + 1e-8)
                b = b / (jnp.linalg.norm(b, axis=-1, keepdims=True) + 1e-8)
                return jnp.sum(a * b, axis=-1)

            pos = cos(z, z_g) / fl.moon_tau
            neg = cos(z, z_p) / fl.moon_tau
            con = -jnp.mean(pos - jnp.logaddexp(pos, neg))
            return classifier_loss(params, batch, cfg) + fl.mu * con

        mom = fl.momentum
        fused = fl.use_fused_sgd

        # DP-SGD is baked at construction (fl is frozen): dp-off builds
        # literally today's step/scan functions, so dp-off runs stay
        # bit-exact without any cache-key machinery.
        if fl.dp_clip > 0:
            sigma = fl.dp_noise_mult * fl.dp_clip
            self._dp = (float(fl.dp_clip), float(sigma))
            self._dp_one = _make_dp(float(fl.dp_clip), float(sigma), False)
            self._dp_many = _make_dp(float(fl.dp_clip), float(sigma), True)
        else:
            self._dp = None
            self._dp_one = self._dp_many = None
        self._dp_base = None        # PRNGKey(fl.dp_seed), built on first use
        self._dp_ctr = 0            # fold_in counter: one fresh key per
                                    # dispatch (per step for train())

        @_scoped("momentum_update")
        def apply_update(params, m, grads, lr):
            """m = mu*m + g; p = p - lr*m. Elementwise, so the same code
            updates a single client or a client-stacked pytree. Opt-in path:
            one fused Pallas pass over the raveled parameter vector instead
            of 2 tree.map passes (the minimal-HBM-traffic update)."""
            if fused:
                from repro.kernels.fused_sgd.ops import fused_sgd_update
                flat_p, unravel = ravel_pytree(params)
                flat_g, _ = ravel_pytree(grads)
                flat_m, _ = ravel_pytree(m)
                p_new, m_new = fused_sgd_update(
                    flat_p, flat_g, flat_m, lr=lr, momentum=mom)
                return unravel(p_new), unravel(m_new)
            m = jax.tree.map(lambda mi, g: mom * mi + g, m, grads)
            params = jax.tree.map(lambda p, mi: p - lr * mi, params, m)
            return params, m

        @_scoped("momentum_update")
        def scaffold_update(params, m, grads, lr, c_glob, c_local):
            # SCAFFOLD (Karimireddy et al. 2020): drift-corrected gradient
            # g + c - c_i (momentum-free, as in the paper's Algorithm 1)
            corr = jax.tree.map(lambda g, c, ci: g + c - ci,
                                grads, c_glob, c_local)
            params = jax.tree.map(lambda p, d: p - lr * d, params, corr)
            return params, m

        dp_one = self._dp_one

        def make_step(loss_fn, update, n_loss_extras):
            if dp_one is None:
                @jax.jit
                def step(params, m, batch, lr, *extras):
                    grads = _grad(loss_fn)(params, batch,
                                           *extras[:n_loss_extras])
                    return update(params, m, grads, lr,
                                  *extras[n_loss_extras:])
            else:
                @jax.jit
                def step(params, m, batch, lr, key, *extras):
                    grads = _grad(loss_fn)(params, batch,
                                           *extras[:n_loss_extras])
                    grads = dp_one(grads, key)
                    return update(params, m, grads, lr,
                                  *extras[n_loss_extras:])
            return step

        self._plain = make_step(plain_loss, apply_update, 0)
        self._prox = make_step(prox_loss, apply_update, 1)
        self._moon = make_step(moon_loss, apply_update, 2)
        self._scaffold = make_step(plain_loss, scaffold_update, 0)

        # -- batched engine: vmap the per-client grad, scan over the padded
        #    step axis. Extras are loop-invariant client-stacked pytrees; the
        #    updates above are elementwise, so they apply to the stack as-is.
        #    Masking is folded into the update arithmetic (ok in {0, 1}):
        #        m' = m + ok*((mu-1)*m + g)      (== mu*m + g   | m)
        #        p' = p - (ok*lr)*m'             (== p - lr*m'  | p)
        #    so an invalid step is a no-op without the extra read/write
        #    passes a jnp.where select would cost (the scan is memory-bound).
        @_scoped("momentum_update")
        def masked_momentum_update(params, m, grads, lr, ok):
            if fused:
                # the flat kernel has no per-client lane — fall back to an
                # explicit select around the fused pass
                p_new, m_new = apply_update(params, m, grads, lr)
                ok = ok.astype(bool)

                def keep(new, old):
                    return jnp.where(_expand_mask(ok, new), new, old)
                return (jax.tree.map(keep, p_new, params),
                        jax.tree.map(keep, m_new, m))

            m = jax.tree.map(
                lambda mi, g: mi + _expand_mask(ok, mi)
                * ((mom - 1.0) * mi + g), m, grads)
            params = jax.tree.map(
                lambda p, mi: p - (_expand_mask(ok, p) * lr) * mi, params, m)
            return params, m

        @_scoped("momentum_update")
        def masked_scaffold_update(params, m, grads, lr, c_glob, c_local, ok):
            # c_glob is ONE unstacked tree (cohort-shared): its (...) leaves
            # broadcast elementwise against the (C, ...) grad/c_local stacks.
            corr = jax.tree.map(lambda g, c, ci: g + c - ci,
                                grads, c_glob, c_local)
            params = jax.tree.map(
                lambda p, d: p - (_expand_mask(ok, p) * lr) * d, params, corr)
            return params, m

        dp_many = self._dp_many

        def make_many(loss_fn, update, extra_axes, broadcast_params, mode,
                      rspec=_WMEAN, has_gw=False, has_dscale=False,
                      has_dref=False):
            # extra_axes: one vmap axis per loss extra — 0 for client-stacked
            # trees, None for cohort-shared trees broadcast inside the jit.
            # mode selects the return contract (see _get_many); rspec /
            # has_* select the reduce family and the adversary transform
            # (all static — the default builds today's jaxpr, bit-for-bit).
            n_loss_extras = len(extra_axes)
            dp = dp_many is not None
            vgrad = _scoped("local_grad")(
                jax.vmap(_grad(loss_fn), in_axes=(0, 0) + extra_axes))

            @jax.jit
            def many(params, batches, valid, lr, *rest):
                # params: (C, ...) pytree — or one client's tree when
                # broadcast_params (stacked inside the jit, so the host never
                # materializes C copies); batches: (C, S, B, ...); valid:
                # (C, S) bool — False steps leave that client's params and
                # momentum untouched.
                key, aggm, gw, ds, dref, extras = _split_head(
                    rest, dp, mode, has_gw, has_dscale, has_dref)
                seed_ref = params       # the lane seed (pre-broadcast/train)
                if broadcast_params:
                    params = _tree_bcast(params, valid.shape[0])
                m = jax.tree.map(jnp.zeros_like, params)
                xs = (jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), batches),
                      jnp.moveaxis(valid, 0, 1).astype(jnp.float32))

                if not dp:
                    def body(carry, x):
                        p, m = carry
                        batch, ok = x
                        g = vgrad(p, batch, *extras[:n_loss_extras])
                        return update(p, m, g, lr, *extras[n_loss_extras:],
                                      ok), None

                    (p, _), _ = jax.lax.scan(body, (params, m), xs)
                else:
                    def body(carry, x):
                        p, m, k = carry
                        batch, ok = x
                        k, sub = jax.random.split(k)
                        g = vgrad(p, batch, *extras[:n_loss_extras])
                        g = dp_many(g, sub)
                        return update(p, m, g, lr, *extras[n_loss_extras:],
                                      ok) + (k,), None

                    (p, _, _), _ = jax.lax.scan(body, (params, m, key), xs)
                if mode == "stack":
                    return p
                if ds is not None:
                    p = _apply_lane_scale(p, ds,
                                          dref if has_dref else seed_ref)
                red = _reduce_stack(p, aggm, gw, rspec)
                return red if mode == "agg" else (red, p)
            return many

        # The vmap in_axes of each loss extra derive from the ONE
        # stacked/shared spec (_EXTRA_STACKED): client-stacked -> 0,
        # cohort-shared -> None (broadcast inside the jit). SCAFFOLD's
        # extras feed the update, not the vmapped loss (n_loss_extras=0):
        # c_glob unstacked broadcasts in tree.map, c_local stays stacked.
        self._many_spec = {
            "plain": (plain_loss, masked_momentum_update, 0),
            "prox": (prox_loss, masked_momentum_update, 1),
            "moon": (moon_loss, masked_momentum_update, 2),
            "scaffold": (plain_loss, masked_scaffold_update, 0),
        }
        self._make_many = make_many

        # -- fused engine: the batched scan, but batches are GATHERED inside
        #    the jit from the device-resident fleet stack (index-only H2D)
        #    and an outer scan walks a hop axis carrying the model stack —
        #    a whole ring lap sequence compiles to one dispatch.
        def make_many_fused(loss_fn, update, extra_axes, broadcast_params,
                            mode, rspec=_WMEAN, has_gw=False,
                            has_dscale=False, has_dref=False):
            n_loss_extras = len(extra_axes)
            dp = dp_many is not None
            vgrad = _scoped("local_grad")(
                jax.vmap(_grad(loss_fn), in_axes=(0, 0) + extra_axes))

            def many_hops(params, images, labels, offsets, rows, plans,
                          valid, lr, *rest):
                # images (total, D) / labels (total,): flat resident stacks,
                # offsets: (K,) first flat row of each client; rows: (H, C)
                # int32 fleet row of each cohort/ring slot per hop; plans:
                # (H, C, S, B) int32 sample indices; valid: (H, C, S).
                # Extras are hop-invariant (rings train variant="plain";
                # star cohorts call with H=1).
                key, aggm, gw, ds, dref, extras = _split_head(
                    rest, dp, mode, has_gw, has_dscale, has_dref)
                seed_ref = params       # the lane seed (pre-broadcast/train)
                if broadcast_params:
                    params = _tree_bcast(params, valid.shape[1])
                p = _run_hops(vgrad, update, n_loss_extras, params, images,
                              labels, offsets, rows, plans, valid, lr,
                              extras, self._item_shape, dp=dp_many,
                              key=key)
                if mode == "stack":
                    return p
                if ds is not None:
                    p = _apply_lane_scale(p, ds,
                                          dref if has_dref else seed_ref)
                red = _reduce_stack(p, aggm, gw, rspec)
                return red if mode == "agg" else (red, p)

            donate = (0,) if (not broadcast_params
                              and _donation_supported()) else ()
            return jax.jit(many_hops, donate_argnums=donate)

        self._make_many_fused = make_many_fused
        # jitted train_many/train_many_fused callables, built on first use:
        # (variant, broadcast_params, mode, rspec, has_gw, has_dscale,
        # has_dref) -> fn. mode is the return contract — "stack": the
        # (C, ...) trained stack; "agg": the in-jit reduced aggregate;
        # "agg_locals": (aggregate, stack).
        self._many_fns: Dict = {}
        self._fused_fns: Dict = {}
        # jitted whole-block schedule dispatches, keyed (variant, hier,
        # rspec, has_dscale) — see train_schedule
        self._sched_fns: Dict = {}

        # data-plane H2D bytes shipped per engine (sequential per-step
        # batches, batched/sharded pixel stacks, fused int32 index plans) —
        # benchmarks reset and read this, as they do ``dispatches``, the
        # count of compiled-call invocations (the fused FedSR round is ONE).
        self.h2d_bytes = 0
        self.dispatches = 0

    def _get_many(self, variant: str, broadcast: bool, mode: str,
                  fused_engine: bool, rspec=_WMEAN, has_gw: bool = False,
                  has_dscale: bool = False, has_dref: bool = False):
        cache = self._fused_fns if fused_engine else self._many_fns
        key = (variant, broadcast, mode, rspec, has_gw, has_dscale, has_dref)
        if key not in cache:
            loss, upd, n_loss = self._many_spec[variant]
            axes = tuple(0 if stacked else None
                         for stacked in self._EXTRA_STACKED[variant][:n_loss])
            make = self._make_many_fused if fused_engine else self._make_many
            cache[key] = make(loss, upd, axes, broadcast, mode, rspec,
                              has_gw, has_dscale, has_dref)
        return cache[key]

    @staticmethod
    def _agg_mode(agg, keep_locals: bool) -> str:
        if agg is None:
            return "stack"              # the stack IS the locals
        return "agg_locals" if keep_locals else "agg"

    def _next_dp_key(self):
        """One fresh PRNG key per DP dispatch (per step for ``train``):
        deterministic from ``fl.dp_seed`` + a host-side counter, so DP
        noise never touches the experiment RNG stream."""
        if self._dp_base is None:
            self._dp_base = jax.random.PRNGKey(self.fl.dp_seed)
        key = jax.random.fold_in(self._dp_base, self._dp_ctr)
        self._dp_ctr += 1
        return key

    # ------------------------------------------------------------------
    def train(
        self,
        params: Pytree,
        client,
        *,
        lr: float,
        epochs: Optional[int] = None,
        rng: Optional[np.random.Generator] = None,
        plan: Optional[np.ndarray] = None,
        variant: str = "plain",
        anchor: Optional[Pytree] = None,
        w_glob: Optional[Pytree] = None,
        w_prev: Optional[Pytree] = None,
        c_glob: Optional[Pytree] = None,
        c_local: Optional[Pytree] = None,
    ) -> Pytree:
        """One client visit, one jitted dispatch per batch (the reference
        engine). Trains on the pre-drawn ``plan`` (a (steps, batch) index
        array — what the planners emit) or draws one from ``rng`` with the
        identical calls (``plan_epoch_indices``), so both paths consume the
        same RNG stream. Per-step host->device batch bytes are metered into
        ``h2d_bytes`` — the sequential engine's data-plane cost, comparable
        with the stacker/index bytes of the other engines."""
        if plan is None:
            if epochs is None or rng is None:
                raise ValueError(
                    "train() needs a pre-drawn plan= or epochs= and rng= "
                    "to draw one")
            plan = plan_epoch_indices(client, self.fl.batch_size, epochs, rng)
        mom = jax.tree.map(jnp.zeros_like, params)
        lr = jnp.asarray(lr, jnp.float32)
        extras = self._extras(variant, anchor, w_glob, w_prev, c_glob, c_local)
        step = {"plain": self._plain, "prox": self._prox,
                "moon": self._moon, "scaffold": self._scaffold}[variant]
        self.last_steps = int(plan.shape[0])
        for sl in plan:
            batch = {"images": client.images[sl], "labels": client.labels[sl]}
            self.h2d_bytes += sum(_h2d_nbytes(v) for v in batch.values())
            self.dispatches += 1
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            head = () if self._dp is None else (self._next_dp_key(),)
            params, mom = step(params, mom, batch, lr, *head, *extras)
        return params

    # ------------------------------------------------------------------
    def train_many(
        self,
        params: Pytree,
        batches: Dict[str, np.ndarray],
        valid: np.ndarray,
        *,
        lr: float,
        variant: str = "plain",
        broadcast: bool = False,
        agg: Optional[np.ndarray] = None,
        agg_gw: Optional[np.ndarray] = None,
        reducer: str = "weighted_mean",
        trim_frac: float = 0.0,
        krum_f: int = 0,
        dscale: Optional[np.ndarray] = None,
        dref: Optional[Pytree] = None,
        keep_locals: bool = False,
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
        anchor: Optional[Pytree] = None,
        w_glob: Optional[Pytree] = None,
        w_prev: Optional[Pytree] = None,
        c_glob: Optional[Pytree] = None,
        c_local: Optional[Pytree] = None,
    ) -> Pytree:
        """One local-training visit for a whole cohort in one compiled call.

        ``params`` and the per-client extras (``w_prev``, ``c_local``) are
        pytrees stacked along a leading client axis C — or, with
        ``broadcast=True``, ``params`` is a single tree that every client
        starts from (stacked device-side, the FedAvg-style fast path).
        Cohort-shared extras (``anchor``, ``w_glob``, ``c_glob``) are single
        unstacked trees, broadcast inside the jit. ``batches``/``valid``
        come from ``stack_client_batches`` / ``stack_plans``
        ((C, S, B, ...) data + (C, S) valid-step mask).

        ``agg`` folds the round's weighted reduce into the SAME dispatch
        (see ``AggSpec.matrix``): a (C,) vector returns the aggregated
        model, a (G, C) matrix the (G, ...) group stack; ghost lanes carry
        weight 0, so no host-side prefix slice is needed.
        ``keep_locals=True`` returns ``(aggregate, (C, ...) stack)``.

        ``reducer`` selects a Byzantine-robust reduce instead of the
        linear contraction (see ``AggSpec.reduce_kwargs``): ``agg`` is
        then the UNCOLLAPSED (G, C) lane-weight matrix (validity mask)
        and ``agg_gw`` the optional (G,) group weights. ``dscale`` is the
        adversary's per-lane delta factor, applied to the trained stack
        before the reduce relative to the lane seed — ``params`` itself,
        or ``dref`` when the input stack is not the seed (the batched
        engine's multi-hop ring path).

        With ``mesh``, every C-stacked input is placed on the mesh's
        ``data_axis`` via ``NamedSharding`` and cohort-shared trees are
        replicated, so the compiled scan partitions the client axis across
        devices; C must then be a multiple of the mesh axis size (callers
        ghost-pad via ``stack_plans(pad_to=...)``).

        Returns the trained (C, ...) stack when ``agg`` is None; per-client
        executed step counts are left in ``self.last_steps_many``.
        """
        self.last_steps_many = np.asarray(valid).sum(axis=1).astype(int)
        self.h2d_bytes += (sum(_h2d_nbytes(v) for v in batches.values())
                           + _h2d_nbytes(valid))
        self.dispatches += 1
        extras = self._extras(variant, anchor, w_glob, w_prev, c_glob, c_local)
        rspec = (reducer, float(trim_frac), int(krum_f))
        fam = self._get_many(variant, broadcast,
                             self._agg_mode(agg, keep_locals), False,
                             rspec, agg_gw is not None, dscale is not None,
                             dref is not None)
        batches = {k: jnp.asarray(v) for k, v in batches.items()}
        valid = jnp.asarray(valid, bool)
        if agg is not None:
            agg = jnp.asarray(agg, jnp.float32)
        if agg_gw is not None:
            agg_gw = jnp.asarray(agg_gw, jnp.float32)
        if dscale is not None:
            dscale = jnp.asarray(dscale, jnp.float32)
        if mesh is not None:
            data_s, shard, repl = self._mesh_placement(
                mesh, data_axis, valid.shape[0], hop_leading=False)
            params = _put(params, repl if broadcast else shard)
            batches = _put(batches, data_s)
            valid = _put(valid, data_s)
            agg, agg_gw, dscale, dref = (
                x if x is None else _put(x, repl)
                for x in (agg, agg_gw, dscale, dref))
            extras = tuple(
                _put(e, shard if s else repl)
                for e, s in zip(extras, self._EXTRA_STACKED[variant]))
        head = self._head(agg, agg_gw, dscale, dref)
        return fam(params, batches, valid, jnp.asarray(lr, jnp.float32),
                   *head, *extras)

    def _head(self, agg, agg_gw, dscale, dref) -> tuple:
        """Assemble the static head of a many()/fused call in the order
        ``_split_head`` unpacks it."""
        head = [] if self._dp is None else [self._next_dp_key()]
        if agg is not None:
            head.append(agg)
            for x in (agg_gw, dscale, dref):
                if x is not None:
                    head.append(x)
        return tuple(head)

    @staticmethod
    def _mesh_placement(mesh, data_axis: str, C: int, hop_leading: bool):
        """NamedSharding placement shared by the sharded and fused engines:
        the (per-visit data, client-stacked, replicated) shardings.
        Per-visit data shards its C axis along ``data_axis`` — with
        ``hop_leading``, after a leading hop axis — and C must divide the
        mesh axis (callers ghost-pad)."""
        n_shards = mesh.shape[data_axis]
        if C % n_shards != 0:
            raise ValueError(
                f"client axis C={C} must be a multiple of mesh axis "
                f"{data_axis!r}={n_shards}; ghost-pad the cohort "
                "(stack_plans/stack_plan_indices pad_to=...)")
        lead = (None, data_axis) if hop_leading else (data_axis,)
        data_s = NamedSharding(mesh, PartitionSpec(*lead))
        shard = NamedSharding(mesh, PartitionSpec(data_axis))
        repl = NamedSharding(mesh, PartitionSpec())
        return data_s, shard, repl

    # ------------------------------------------------------------------
    def train_many_fused(
        self,
        params: Pytree,
        plane,
        rows: np.ndarray,
        plans: np.ndarray,
        valid: np.ndarray,
        *,
        lr: float,
        variant: str = "plain",
        broadcast: bool = False,
        agg: Optional[np.ndarray] = None,
        agg_gw: Optional[np.ndarray] = None,
        reducer: str = "weighted_mean",
        trim_frac: float = 0.0,
        krum_f: int = 0,
        dscale: Optional[np.ndarray] = None,
        dref: Optional[Pytree] = None,
        keep_locals: bool = False,
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
        anchor: Optional[Pytree] = None,
        w_glob: Optional[Pytree] = None,
        w_prev: Optional[Pytree] = None,
        c_glob: Optional[Pytree] = None,
        c_local: Optional[Pytree] = None,
    ) -> Pytree:
        """A hop sequence of cohort visits in ONE compiled call against the
        device-resident data plane (``DeviceDataPlane``).

        ``rows`` (H, C) int32, ``plans`` (H, C, S, B) int32 and ``valid``
        (H, C, S) bool come from ``stack_plan_indices``; they are the
        ENTIRE per-call H2D data payload — each step's pixels are gathered
        from ``plane`` inside the jit. Hop h trains fleet row ``rows[h, c]``
        on plan ``plans[h, c]`` starting from the carried (C, ...) model
        stack, with momentum reset per visit, so a FedSR/Ring round (H =
        R*K hops) is one dispatch instead of R*K. Star cohorts call with
        H=1 and behave exactly like ``train_many``.

        ``agg``/``keep_locals`` fold the weighted reduce into the same
        dispatch, exactly as in ``train_many`` — with a collapsed (C,)
        ``agg`` the whole FedSR round (broadcast, ring laps, cloud reduce)
        is ONE compiled call.

        ``broadcast=True`` stacks a single params tree device-side (the
        FedAvg/ring-seed fast path). With ``broadcast=False`` the params
        stack is DONATED to the computation on accelerator backends — the
        caller's buffer is consumed and updated in place; pass a fresh
        stack. ``mesh`` shards the C axis like ``train_many`` (the plane
        itself was placed at construction).
        """
        rows = np.asarray(rows, np.int32)
        plans = np.asarray(plans, np.int32)
        valid = np.asarray(valid, bool)
        self.last_steps_many = valid.sum(axis=(0, 2)).astype(int)
        self.h2d_bytes += rows.nbytes + plans.nbytes + valid.nbytes
        self.dispatches += 1
        extras = self._extras(variant, anchor, w_glob, w_prev, c_glob, c_local)
        rspec = (reducer, float(trim_frac), int(krum_f))
        fam = self._get_many(variant, broadcast,
                             self._agg_mode(agg, keep_locals), True,
                             rspec, agg_gw is not None, dscale is not None,
                             dref is not None)
        if agg is not None:
            agg = jnp.asarray(agg, jnp.float32)
        if agg_gw is not None:
            agg_gw = jnp.asarray(agg_gw, jnp.float32)
        if dscale is not None:
            dscale = jnp.asarray(dscale, jnp.float32)
        if mesh is not None:
            hop_s, shard, repl = self._mesh_placement(
                mesh, data_axis, valid.shape[1], hop_leading=True)
            params = _put(params, repl if broadcast else shard)
            rows, plans, valid = (_put(x, hop_s)
                                  for x in (rows, plans, valid))
            agg, agg_gw, dscale, dref = (
                x if x is None else _put(x, repl)
                for x in (agg, agg_gw, dscale, dref))
            extras = tuple(
                _put(e, shard if s else repl)
                for e, s in zip(extras, self._EXTRA_STACKED[variant]))
        head = self._head(agg, agg_gw, dscale, dref)
        return fam(params, plane.images, plane.labels, plane.offsets,
                   jnp.asarray(rows), jnp.asarray(plans), jnp.asarray(valid),
                   jnp.asarray(lr, jnp.float32), *head, *extras)

    # ------------------------------------------------------------------
    # Schedule dispatch: a whole eval-to-eval block of rounds in ONE
    # compiled call (see core.plan.Schedule / engines.fused.run_schedule)

    # leading replicated axes of each schedule array before the sharded
    # lane axis C (None: fully replicated — no lane axis)
    _SCHED_LEAD = {
        "rows": 2, "plans": 2, "valid": 2,          # (n, H|R, C, ...)
        "ids": 1, "aggv": 1, "kl": 1, "mw": 1,
        "use_prev": 1, "seed": 1, "dscale": 1,      # (n, C)
        "lr": None, "frac": None,                   # (n,)
        "wg": 2, "aggw": 2,                         # (n, G, C)
        "aggg": None, "gwv": None,                  # (n, G) — replicated
    }

    def _make_schedule(self, variant: str, hier: bool, rspec=_WMEAN,
                       has_dscale: bool = False):
        """Build the jitted block dispatch: an outer ``lax.scan`` over the
        round axis whose carry is ``(w_glob, algo_state)``. Each round body
        broadcasts the carried global, runs the flat hop scan
        (``_run_hops``), contracts the round's aggregation vector and
        updates the state carry in place — so MOON's prev-locals and
        SCAFFOLD's variates live on device across the whole block. With
        ``hier`` (HierFAVG) the body is instead R chained edge iterations:
        a scan over the first R-1 (in-scan (G, C) per-edge reduce seeding
        the next iteration's lanes) plus a peeled final iteration that
        applies the collapsed cloud weights exactly like the per-round
        engine does — keeping chunked vs per-round bit-parity.

        ``rspec``/``has_dscale`` fold the robust reduce and the adversary's
        per-lane delta transform into the same block dispatch (the robust
        operands ``aggw``/``aggg`` — or ``gwv`` for hier — and ``dscale``
        ship as extra xs lanes); DP-SGD threads a key through both scan
        levels. All static — the defaults build today's jaxpr."""
        from repro.core.state import gather_rows, scaffold_step, scatter_rows

        loss_fn, update, n_loss = self._many_spec[variant]
        axes = tuple(0 if stacked else None
                     for stacked in self._EXTRA_STACKED[variant][:n_loss])
        vgrad = _scoped("local_grad")(
            jax.vmap(self._grad(loss_fn), in_axes=(0, 0) + axes))
        dp_many = self._dp_many
        dp = dp_many is not None
        robust = rspec[0] != "weighted_mean"

        def round_extras(w, st, x):
            """The plan's extras, resolved from the scan carry: GLOBAL is
            the carried ``w``; StateRefs gather their lanes' rows."""
            if variant == "prox":
                return (w,)                         # FedProx anchor
            if variant == "moon":
                rows = gather_rows(st["prev"], x["ids"])
                w_prev = jax.tree.map(
                    lambda r, wl: jnp.where(_expand_mask(x["use_prev"], r),
                                            r, wl[None]),
                    rows, w)
                return (w, w_prev)
            if variant == "scaffold":
                return (st["c"], gather_rows(st["ci"], x["ids"]))
            return ()

        def update_carry(w_before, st, x, p):
            if variant == "moon":
                return dict(st, prev=scatter_rows(st["prev"], x["ids"], p))
            if variant == "scaffold":
                c, ci = scaffold_step(st["c"], st["ci"], x["ids"], p,
                                      w_before, x["kl"], x["mw"], x["frac"])
                return dict(st, c=c, ci=ci)
            return st

        def sched(w0, carry, images, labels, offsets, xs, *dpk):
            def train_group(params, rows, plans, valid, lr, extras, key):
                return _run_hops(vgrad, update, n_loss, params, images,
                                 labels, offsets, rows, plans, valid, lr,
                                 extras, self._item_shape, dp=dp_many,
                                 key=key)

            if hier:
                def round_step(w, st, x, key):
                    seed = x["seed"]

                    def one_iter(E, xi, reduce_fn, sub):
                        params = jax.tree.map(lambda t: t[seed], E)
                        p = train_group(params, xi["rows"][None],
                                        xi["plans"][None], xi["valid"][None],
                                        x["lr"], (), sub)
                        if has_dscale:
                            p = _apply_lane_scale(p, x["dscale"], params)
                        return reduce_fn(p)

                    def inter(p):
                        if robust:
                            return _robust_agg(p, x["wg"], None, *rspec)
                        return _tree_agg(p, x["wg"])

                    def final(p):
                        if robust:
                            return _robust_agg(p, x["wg"], x["gwv"],
                                               *rspec)
                        return _tree_agg(p, x["aggv"])

                    E = _tree_bcast(w, x["wg"].shape[0])
                    head = {k: x[k][:-1]
                            for k in ("rows", "plans", "valid")}
                    last = {k: x[k][-1] for k in ("rows", "plans", "valid")}
                    if dp:
                        def istep(c, xi):
                            Ec, kc = c
                            kc, sub = jax.random.split(kc)
                            return (one_iter(Ec, xi, inter, sub), kc), None

                        (E, key), _ = jax.lax.scan(istep, (E, key), head)
                        key, sub = jax.random.split(key)
                        return one_iter(E, last, final, sub), st
                    E, _ = jax.lax.scan(
                        lambda E, xi: (one_iter(E, xi, inter, None), None),
                        E, head)
                    return one_iter(E, last, final, None), st
            else:
                def round_step(w, st, x, key):
                    params = _tree_bcast(w, x["valid"].shape[1])
                    p = train_group(params, x["rows"], x["plans"],
                                    x["valid"], x["lr"],
                                    round_extras(w, st, x), key)
                    if has_dscale:
                        p = _apply_lane_scale(p, x["dscale"], w)
                    if robust:
                        w_new = _robust_agg(p, x["aggw"], x["aggg"],
                                            *rspec)
                    else:
                        w_new = _tree_agg(p, x["aggv"])
                    return w_new, update_carry(w, st, x, p)

            if dp:
                def body(rc, x):
                    w, st, k = rc
                    k, sub = jax.random.split(k)
                    w_new, st_new = round_step(w, st, x, sub)
                    return (w_new, st_new, k), None

                (w, out, _), _ = jax.lax.scan(body, (w0, carry, dpk[0]), xs)
            else:
                def body(rc, x):
                    w, st = rc
                    w_new, st_new = round_step(w, st, x, None)
                    return (w_new, st_new), None

                (w, out), _ = jax.lax.scan(body, (w0, carry), xs)
            return w, out

        return jax.jit(sched)

    def train_schedule(
        self,
        params: Pytree,
        plane,
        xs: Dict[str, np.ndarray],
        carry: Dict[str, Pytree],
        *,
        variant: str = "plain",
        hier: bool = False,
        reducer: str = "weighted_mean",
        trim_frac: float = 0.0,
        krum_f: int = 0,
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
    ) -> Pytree:
        """An entire block of FL rounds as ONE compiled dispatch.

        ``xs`` stacks the block's per-round schedule along a leading round
        axis ``n`` (built by ``engines.fused.FusedEngine.run_schedule``):
        ``rows``/``plans``/``valid`` as in ``train_many_fused`` but
        (n, H, C, ...), per-round ``lr`` (n,) and collapsed aggregation
        vectors ``aggv`` (n, C) — plus the variant's state-carry lanes
        (``ids``, MOON's ``use_prev``, SCAFFOLD's ``kl``/``mw``/``frac``).
        These int32/bool/f32 arrays are the block's ENTIRE H2D payload.

        ``carry`` is the algorithm's device-resident state (``core.state``
        client stacks); the compiled scan threads ``(w_glob, carry)``
        round to round, so a block of ``n`` fused FedSR rounds — broadcast,
        hop scan, cloud reduce, n times — is literally one compiled call
        (``dispatches`` records 1). Returns ``(w_glob, carry)``.

        ``reducer``/``trim_frac``/``krum_f`` select the robust reduce for
        every round of the block; the robust operands (``aggw``/``aggg``,
        or ``gwv`` for hier) and the adversary's ``dscale`` arrive as extra
        ``xs`` lanes — so an attacked, robustly-aggregated block is still
        ONE dispatch.

        ``mesh`` shards every lane axis C over ``data_axis`` exactly like
        ``train_many_fused`` (the round axis n stays unsharded — it is a
        sequential scan); the state carry is replicated (its K + 1 rows
        need not divide the mesh).
        """
        nbytes = sum(np.asarray(v).nbytes for v in xs.values())
        self.h2d_bytes += nbytes
        trace.count("h2d_bytes", nbytes)
        self.dispatches += 1
        rspec = (reducer, float(trim_frac), int(krum_f))
        has_dscale = "dscale" in xs
        key = (variant, hier, rspec, has_dscale)
        if key not in self._sched_fns:
            self._sched_fns[key] = self._make_schedule(
                variant, hier, rspec, has_dscale)
        fn = self._sched_fns[key]
        if mesh is not None:
            C = xs["valid"].shape[2]
            if C % mesh.shape[data_axis] != 0:
                raise ValueError(
                    f"schedule lane axis C={C} must be a multiple of mesh "
                    f"axis {data_axis!r}={mesh.shape[data_axis]}")
        with trace.span("put"):
            if mesh is not None:
                repl = NamedSharding(mesh, PartitionSpec())
                placed = {}
                for k, v in xs.items():
                    lead = self._SCHED_LEAD[k]
                    if lead is None:
                        placed[k] = _put(v, repl)
                    else:
                        spec = PartitionSpec(*([None] * lead + [data_axis]))
                        placed[k] = _put(v, NamedSharding(mesh, spec))
                xs = placed
                params = _put(params, repl)
                carry = _put(carry, repl)
            else:
                xs = {k: jnp.asarray(v) for k, v in xs.items()}
        dpk = () if self._dp is None else (self._next_dp_key(),)
        with trace.span("dispatch"):
            return fn(params, carry, plane.images, plane.labels,
                      plane.offsets, xs, *dpk)

    # which extras carry a leading client axis (True) vs are cohort-shared
    # single trees (False) — order matches ``_extras``
    _EXTRA_STACKED = {
        "plain": (),
        "prox": (False,),               # anchor
        "moon": (False, True),          # w_glob, w_prev
        "scaffold": (False, True),      # c_glob, c_local
    }

    @staticmethod
    def _extras(variant, anchor, w_glob, w_prev, c_glob, c_local) -> tuple:
        try:
            return {
                "plain": (),
                "prox": (anchor,),
                "moon": (w_glob, w_prev),
                "scaffold": (c_glob, c_local),
            }[variant]
        except KeyError:
            raise ValueError(variant) from None
