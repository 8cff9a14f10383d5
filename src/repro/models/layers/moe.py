"""Mixture-of-Experts layer with two routers and explicit EP dispatch.

Routers:
* ``topk``    — softmax top-k with load-balancing aux loss (Switch/GShard);
* ``sigmoid`` — DeepSeek-V3 style sigmoid scores + selection bias, gates
                renormalised over the selected experts;
* ``hash``    — **the paper's technique**: BinomialHash consistent routing of
                token-ids to experts ("Hash Layers" style).  Balance comes
                from the paper's Eq. (3) bound instead of an aux loss, and
                monotonicity gives elastic expert scaling: growing E moves
                only ~k/E of the token assignments (benchmarked).

Dispatch is sort-based (megablocks-lite): tokens are argsorted by expert id,
ranked within expert via searchsorted offsets, and scattered into a fixed
(E_local, C, D) buffer — no (B, S, E, C) one-hot dispatch tensors.

Distribution: experts are sharded over the ``model`` axis (EP).  Under a
mesh the layer runs inside ``shard_map``: dispatch is device-local (tokens
are replicated over ``model``), expert FFNs run on the local expert slice
(weights optionally ZeRO-3-gathered over ``data``), and partial outputs are
``psum``-combined over ``model`` — the same reduce the TP FFN would need, so
EP costs no extra collective volume beyond ZeRO-3 weight gathers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.binomial_jax import mix32
from repro.core.registry import make_bulk
from repro.models.layers.common import dense_init, init_mlp, apply_mlp
from repro.sharding.rules import current_mesh, expert_layout, logical, shard

GOLDEN32 = np.uint32(0x9E3779B9)


def init_moe(key, cfg: ArchConfig):
    m = cfg.moe
    D, E, Fe = cfg.d_model, m.num_experts, m.d_ff_expert
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=0.006),
        "experts_wi": dense_init(ks[1], (E, D, Fe), dt),
        "experts_wg": dense_init(ks[2], (E, D, Fe), dt),
        "experts_wo": dense_init(ks[3], (E, Fe, D), dt, scale=0.02 / np.sqrt(2 * cfg.num_layers)),
    }
    if m.router == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if m.shared_experts > 0:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=m.shared_experts * Fe)
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(p, x, token_ids, layer_salt, cfg: ArchConfig):
    """-> expert_ids (B,S,K) int32, gates (B,S,K) f32, aux_loss scalar."""
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    if m.router == "hash":
        # The paper's consistent-hash router: key = mix(token_id, salt, k).
        # layer_salt may be a traced scan counter — mix with jnp ops.
        # All K salted key families are built as one broadcast (B,S,K)
        # tensor and routed by ONE lookup dispatch — the lookup is
        # elementwise over its key operand, so this is bit-exact with the
        # former per-k loop while collapsing K compiled-call dispatches
        # (and K ω-unrolled producers for XLA to fuse) into one.
        keys = token_ids.astype(jnp.uint32)
        salt0 = jnp.asarray(layer_salt, jnp.uint32) * np.uint32(1000003)
        k_salts = (np.arange(K) * 7919 + 1).astype(np.uint32)  # (K,)
        salts = (salt0 + k_salts) * GOLDEN32
        kk = mix32(keys[..., None] ^ salts)  # (B, S, K)
        # which consistent-hash lookup routes tokens is a BULK_ENGINES
        # choice (DESIGN.md §10) — same salted-key construction, pluggable
        # lookup body, so engine comparisons share one dispatch shape
        eng = make_bulk(m.router_hash_engine)
        if m.router_dynamic_n:
            # expert count as a traced operand of the router lookup: when
            # route() runs eagerly (routing sweeps, placement studies) one
            # compiled trace serves every E. Inside a jitted model step E
            # is a static config constant, so this cannot prevent the
            # enclosing step from retracing on resize.
            expert_ids = eng.lookup_dyn(kk, jnp.uint32(E), omega=m.router_hash_omega)
        else:
            expert_ids = eng.lookup_vec(kk, E, omega=m.router_hash_omega)
        gates = jnp.full(expert_ids.shape, 1.0 / K, jnp.float32)
        return expert_ids, gates, jnp.float32(0.0)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"]
        _, expert_ids = jax.lax.top_k(sel, K)
        g = jnp.take_along_axis(scores, expert_ids, axis=-1)
        gates = g / jnp.maximum(jnp.sum(g, -1, keepdims=True), 1e-9)
        aux = jnp.float32(0.0)  # DS-V3 is aux-loss-free (bias-based balancing)
    else:  # topk softmax
        probs = jax.nn.softmax(logits, axis=-1)
        g, expert_ids = jax.lax.top_k(probs, K)
        gates = g / jnp.maximum(jnp.sum(g, -1, keepdims=True), 1e-9)
        # Switch-style load-balance loss
        me = jnp.mean(probs.reshape(-1, E), axis=0)
        onehot = jax.nn.one_hot(expert_ids.reshape(-1), E, dtype=jnp.float32)
        ce = jnp.mean(jnp.max(onehot, axis=1)[:, None] * onehot, axis=0) * E
        aux = m.aux_loss_weight * E * jnp.sum(me * ce)
    return expert_ids.astype(jnp.int32), gates.astype(jnp.float32), aux


# ---------------------------------------------------------------------------
# sort-based local dispatch (runs per model-shard on its expert slice)
# ---------------------------------------------------------------------------


def _expert_ffn(buf, wi, wg, wo):
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, wg)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _routing_plan(expert_ids, gates, e_offset, E_local, C, N, K):
    """Sort-based assignment plan for the local expert slice."""
    flat_e = expert_ids.reshape(-1)
    flat_g = gates.reshape(-1)
    tok = jnp.arange(N * K, dtype=jnp.int32) // K

    local = (flat_e >= e_offset) & (flat_e < e_offset + E_local)
    le = jnp.where(local, flat_e - e_offset, E_local)  # E_local = overflow bin
    order = jnp.argsort(le, stable=True)
    se = le[order]
    stok = tok[order]
    sg = flat_g[order]

    offsets = jnp.searchsorted(se, jnp.arange(E_local, dtype=se.dtype))
    rank = jnp.arange(N * K, dtype=jnp.int32) - offsets[jnp.clip(se, 0, E_local - 1)]
    keep = (se < E_local) & (rank < C)
    slot = jnp.where(keep, se * C + rank, E_local * C)  # last row = dump slot
    return slot, stok, sg, keep


def _scatter_buf(x_flat, slot, stok, keep, E_local, C):
    D = x_flat.shape[-1]
    buf = jnp.zeros((E_local * C + 1, D), x_flat.dtype)
    return buf.at[slot].add(x_flat[stok] * keep[:, None].astype(x_flat.dtype))


def _combine(out_buf_flat, slot, stok, sg, keep, N, dtype):
    D = out_buf_flat.shape[-1]
    contrib = out_buf_flat[jnp.clip(slot, 0, out_buf_flat.shape[0] - 1)]
    w = (sg * keep).astype(dtype)[:, None]
    return jnp.zeros((N, D), dtype).at[stok].add(contrib * w)


def _dispatch_local(x_flat, expert_ids, gates, wi, wg, wo, e_offset, E_local, C):
    """x_flat (N,D); expert_ids/gates (N,K); weights local (E_local,...).

    Gather/scatter touch only the E_local*C buffer rows (the kept
    assignments), not all N*K assignment slots — 10-15x less dispatch
    traffic when this model-shard owns 1/16 of the experts (§Perf cell 3).
    """
    N, D = x_flat.shape
    K = expert_ids.shape[-1]
    slot, stok, sg, keep = _routing_plan(expert_ids, gates, e_offset, E_local, C, N, K)
    # invert slot -> source assignment (kept slots are collision-free)
    src = jnp.full((E_local * C + 1,), -1, jnp.int32)
    src = src.at[slot].set(jnp.arange(N * K, dtype=jnp.int32))[: E_local * C]
    valid = src >= 0
    srcc = jnp.clip(src, 0)
    rows = x_flat[stok[srcc]] * valid[:, None].astype(x_flat.dtype)
    out_buf = _expert_ffn(rows.reshape(E_local, C, D), wi, wg, wo).reshape(E_local * C, D)
    w = (sg[srcc] * valid).astype(x_flat.dtype)
    y = jnp.zeros((N, D), x_flat.dtype)
    return y.at[jnp.where(valid, stok[srcc], N)].add(out_buf * w[:, None], mode="drop")


def _capacity(cfg: ArchConfig, n_local_tokens: int) -> int:
    m = cfg.moe
    return max(1, int(m.capacity_factor * n_local_tokens * m.top_k / m.num_experts))


# ---------------------------------------------------------------------------
# dense GShard path for tiny token counts (decode): the (N,E,C) dispatch
# tensors are trivial at serve batch sizes, and pure einsums let GSPMD keep
# expert weights fully sharded (E over model, D over data) with only
# KB..MB-sized activation psums — no shard_map boundary, no weight motion.
# ---------------------------------------------------------------------------


def _gshard_masks(expert_ids, gates, E: int, C: int):
    """expert_ids/gates (N,K) -> dispatch (N,E,C) bool-ish, combine (N,E,C)."""
    N, K = expert_ids.shape
    oh = jax.nn.one_hot(expert_ids.reshape(-1), E, dtype=jnp.float32)  # (N*K, E)
    pos = jnp.cumsum(oh, axis=0) - oh
    rank = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)  # (N*K,)
    keep = (rank < C).astype(jnp.float32)
    disp = oh * keep[:, None]  # (N*K, E)
    disp_c = disp[:, :, None] * jax.nn.one_hot(jnp.minimum(rank, C - 1), C)[:, None, :]
    dispatch = disp_c.reshape(N, K, E, C).sum(axis=1)
    combine = (disp_c * gates.reshape(-1)[:, None, None]).reshape(N, K, E, C).sum(axis=1)
    return dispatch, combine


def _dense_moe(p, x_flat, expert_ids, gates, cfg: ArchConfig, C: int):
    m = cfg.moe
    E = m.num_experts
    dispatch, combine = _gshard_masks(expert_ids, gates, E, C)
    # pin weight layouts to the ambient expert layout; under "tp" (serving)
    # experts are replicated over model with F sharded — per-expert tensor
    # parallelism, which is what 1-token-per-expert capacities want
    if expert_layout() == "tp":
        wi = shard(p["experts_wi"], None, "fsdp", "tp")
        wg = shard(p["experts_wg"], None, "fsdp", "tp")
        wo = shard(p["experts_wo"], None, "tp", "fsdp")
        espec, hspec = (None, None, "fsdp"), (None, None, "tp")
    else:
        wi = shard(p["experts_wi"], "tp", "fsdp", None)
        wg = shard(p["experts_wg"], "tp", "fsdp", None)
        wo = shard(p["experts_wo"], "tp", None, "fsdp")
        espec, hspec = ("tp", None, "fsdp"), ("tp", None, None)
    buf = jnp.einsum("nec,nd->ecd", dispatch.astype(x_flat.dtype), x_flat)
    buf = shard(buf, *espec)
    # weights as dot LHS: layout shuffles land on the tiny C-sized
    # activations (e,f,c)/(e,d,c), never on the weight streams
    hi = jnp.einsum("edf,ecd->efc", wi, buf)
    hg = jnp.einsum("edf,ecd->efc", wg, buf)
    h = shard(jax.nn.silu(hi) * hg, hspec[0], hspec[2], hspec[1])
    out = jnp.einsum("efd,efc->edc", wo, h)
    out = shard(out, espec[0], espec[2], espec[1])
    y = jnp.einsum("edc,nec->nd", out, combine.astype(x_flat.dtype))
    return y


# ---------------------------------------------------------------------------
# full layer
# ---------------------------------------------------------------------------


def apply_moe(p, x, token_ids, layer_salt, cfg: ArchConfig):
    """x (B,S,D) -> (B,S,D), aux_loss.  token_ids (B,S) int32 (hash router)."""
    m = cfg.moe
    B, S, D = x.shape
    expert_ids, gates, aux = route(p, x, token_ids, layer_salt, cfg)

    mesh = current_mesh()
    if mesh is None:
        C = _capacity(cfg, B * S)
        y = _dispatch_local(
            x.reshape(-1, D), expert_ids.reshape(-1, m.top_k), gates.reshape(-1, m.top_k),
            p["experts_wi"], p["experts_wg"], p["experts_wo"], 0, m.num_experts, C,
        ).reshape(B, S, D)
    else:
        tp = mesh.shape["model"]
        dp_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
        n_local = (B // dp) * S
        C = _capacity(cfg, n_local)
        E_local = m.num_experts // tp
        fsdp_w = logical("tp", "fsdp", None)  # (E, D, Fe) spec
        fsdp_wo = logical("tp", None, "fsdp")
        gathered = fsdp_w[1] is not None
        n_local = (B // dp) * S
        if n_local * m.top_k <= 4 * m.num_experts:
            # Few tokens per expert (decode / small serve batches): a
            # shard_map dispatch would force per-layer weight-slice copies at
            # its boundary and ZeRO-3 gathers would stream the full expert
            # slice (GBs/layer) for a handful of tokens. The dense-GShard
            # einsum path keeps weights fully sharded (E over model, D over
            # data) — only MB-sized activation psums move (§Perf cell 2).
            Cg = max(1, int(m.capacity_factor * B * S * m.top_k / m.num_experts))
            y = _dense_moe(
                p, x.reshape(-1, D), expert_ids.reshape(-1, m.top_k),
                gates.reshape(-1, m.top_k), cfg, Cg,
            ).reshape(B, S, D)
            y = shard(y, "dp", None, None)
        else:

            def body(xs, eids, gs, wi, wg, wo):
                # per-device: xs (Bl,S,D); weights (E_local, D[/data], Fe)
                midx = jax.lax.axis_index("model")
                if gathered:
                    wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
                    wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
                    wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
                y = _dispatch_local(
                    xs.reshape(-1, D), eids.reshape(-1, m.top_k), gs.reshape(-1, m.top_k),
                    wi, wg, wo, midx * E_local, E_local, C,
                )
                return jax.lax.psum(y, "model").reshape(xs.shape)

            dspec = P(dp_axes, None, None)
            y = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(dspec, dspec, dspec, fsdp_w, fsdp_w, fsdp_wo),
                out_specs=dspec,
                check_vma=False,
            )(x, expert_ids, gates, p["experts_wi"], p["experts_wg"], p["experts_wo"])

    if m.shared_experts > 0:
        y = y + apply_mlp(p["shared"], x, cfg)
    return y, aux
