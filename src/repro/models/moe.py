"""Mixture-of-Experts layer with structure-aware (sorted) dispatch.

Paper tie-in (DESIGN.md §4): the token->expert assignment matrix is an
unstructured sparse operator -- the R-MAT case.  Multiplying through it
directly would be a random gather per token (the paper's demand-miss
pathology).  We *permute into structure* instead: sort token slots by expert
id, making the dispatch block-diagonal (the FD case), then run dense
per-expert GEMMs.  This is the paper's row/column-permutation argument run
in reverse, and `core.structure.analyze` can quantify the before/after
(see tests/test_moe.py::test_dispatch_restructuring).

Expert-parallel sharding: expert weights carry a leading E dim sharded on
the 'model' mesh axis; the dispatch buffers get sharding constraints so the
token exchange lowers to an all-to-all inside the pod.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.api import constrain
from .common import dense_init, dtype_of

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, ff, e = cfg.d_model, m.d_expert_ff, m.n_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 8)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], e * d, ff, dt).reshape(e, d, ff),
        "w_up": dense_init(ks[2], e * d, ff, dt).reshape(e, d, ff),
        "w_down": dense_init(ks[3], e * ff, d, dt).reshape(e, ff, d),
    }
    if m.n_shared_experts:
        se = m.n_shared_experts
        p["shared_gate"] = dense_init(ks[4], se * d, ff, dt).reshape(se, d, ff)
        p["shared_up"] = dense_init(ks[5], se * d, ff, dt).reshape(se, d, ff)
        p["shared_down"] = dense_init(ks[6], se * ff, d, dt).reshape(se, ff, d)
    return p


def apply_moe(p: Params, cfg: ModelConfig, x: jax.Array,
              capacity: Optional[int] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (y, aux_losses).

    Sorted-dispatch with fixed expert capacity (dropped tokens pass through
    the residual only, standard practice).
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                   # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (load balance + router z-loss) ----
    me = probs.mean(axis=0)                                  # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (t * k))
    aux = {
        "moe_balance": e * jnp.sum(me * ce) * m.aux_loss_weight,
        "moe_zloss": (jax.nn.logsumexp(logits, -1) ** 2).mean()
        * m.router_z_loss,
    }

    # ---- restructuring: sort slots by expert (unstructured -> blocked) ----
    cap = capacity or int(-(-t * k // e) * m.capacity_factor)
    flat_e = top_e.reshape(-1)                               # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)                              # the permutation
    se_, sw_, st_ = flat_e[order], flat_w[order], flat_tok[order]
    # position of each slot within its expert's block
    pos_in_e = jnp.arange(t * k) - jnp.searchsorted(se_, se_, side="left")
    keep = pos_in_e < cap
    slot = se_ * cap + pos_in_e                              # (T*k,)
    slot = jnp.where(keep, slot, e * cap)                    # overflow slot

    # dispatch: (E*cap+1, d) buffer; one extra row swallows dropped tokens
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].set(xt[st_])
    buf = buf[: e * cap].reshape(e, cap, d)
    # expert dim on 'model' (EP), capacity on 'dp': the scatter above lowers
    # to the dispatch all-to-all between the token-sharded and expert-sharded
    # layouts (DESIGN.md §4.1)
    buf = constrain(buf, "model", "dp", None)

    # expert FFNs: dense per-expert GEMMs (the block-diagonal multiply)
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])         # (E, cap, d)
    out = constrain(out, "model", "dp", None)

    # combine: weighted scatter-add back to token order
    out_flat = out.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, e * cap - 1)],
                         0.0)
    y = jnp.zeros((t, d), x.dtype).at[st_].add(
        (gathered * sw_[:, None]).astype(x.dtype))

    # shared experts (Kimi K2): always-on, added to every token
    if m.n_shared_experts:
        hs = jnp.einsum("td,edf->etf", xt, p["shared_gate"])
        hs = jax.nn.silu(hs) * jnp.einsum("td,edf->etf", xt, p["shared_up"])
        y = y + jnp.einsum("etf,efd->td", hs, p["shared_down"]).astype(x.dtype)

    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Sharded (EP) dispatch under shard_map
# ---------------------------------------------------------------------------
#
# The global sorted-scatter above is the *reference semantics*, but GSPMD
# cannot shard a data-dependent scatter across 1M tokens: the SPMD partition
# replicates the dispatch buffer (1.7 TB of temps for kimi-k2 at
# train_4k).  The scalable realization mirrors the paper's per-thread row
# blocks: every data shard restructures ITS tokens locally (local sort ->
# local capacity), every model shard owns E/M experts and multiplies only
# its slice, and one psum over 'model' recombines.  Dispatch itself moves
# zero bytes (tokens are already replicated over 'model'); the combine is
# the only collective.

def _dp_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def apply_moe_sharded(p: Params, cfg: ModelConfig, x: jax.Array
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """shard_map MoE: per-data-shard dispatch, per-model-shard experts."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.api import current_mesh

    mesh = current_mesh()
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    dp = _dp_axes(mesh)
    n_model = mesh.shape["model"]
    e_local = e // n_model

    def local_fn(xs, router, wg, wu, wd):
        # xs: (b_local, s, d); router: (d, E) replicated;
        # wg/wu/wd: (E/M, d, ff) local expert slice.
        bl = xs.shape[0]
        t = bl * s
        xt = xs.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router           # (t, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        # aux losses from globally-averaged stats (pmean over dp)
        me = jax.lax.pmean(probs.mean(axis=0), dp[0]) if len(dp) == 1 else \
            jax.lax.pmean(jax.lax.pmean(probs.mean(axis=0), dp[0]), dp[1])
        ce_local = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
            1.0 / (t * k))
        ce = jax.lax.pmean(ce_local, dp[0]) if len(dp) == 1 else \
            jax.lax.pmean(jax.lax.pmean(ce_local, dp[0]), dp[1])
        zloss = (jax.nn.logsumexp(logits, -1) ** 2).mean()
        zloss = jax.lax.pmean(zloss, dp[0]) if len(dp) == 1 else \
            jax.lax.pmean(jax.lax.pmean(zloss, dp[0]), dp[1])
        aux = {"moe_balance": e * jnp.sum(me * ce) * m.aux_loss_weight,
               "moe_zloss": zloss * m.router_z_loss}

        # local restructuring: sort this shard's slots by expert id
        cap = max(int(-(-t * k // e) * m.capacity_factor), 1)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(flat_e)
        se_, sw_, st_ = flat_e[order], flat_w[order], flat_tok[order]
        pos_in_e = jnp.arange(t * k) - jnp.searchsorted(se_, se_, side="left")

        # this model shard's expert range
        j = jax.lax.axis_index("model")
        e0 = j * e_local
        le = se_ - e0
        in_range = (le >= 0) & (le < e_local) & (pos_in_e < cap)
        slot = jnp.where(in_range, le * cap + pos_in_e, e_local * cap)

        tok_buf = jnp.zeros((e_local * cap + 1,), jnp.int32) \
            .at[slot].set(st_.astype(jnp.int32), mode="drop")[:-1]
        wgt_buf = jnp.zeros((e_local * cap + 1,), jnp.float32) \
            .at[slot].set(jnp.where(in_range, sw_, 0.0), mode="drop")[:-1]

        # gather only the local experts' rows: (E/M * cap, d)
        gx = jnp.take(xt, tok_buf, axis=0).reshape(e_local, cap, d)
        h = jnp.einsum("ecd,edf->ecf", gx, wg)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", gx, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_local * cap, d)

        y = jnp.zeros((t, d), jnp.float32).at[tok_buf].add(
            out.astype(jnp.float32) * wgt_buf[:, None])
        # combine across expert shards; bf16 halves the EP wire bytes and
        # only <= top_k shards contribute nonzero per token (knob: §Perf)
        from . import tuning
        if tuning.moe_combine_bf16:
            y = jax.lax.psum(y.astype(jnp.bfloat16), "model")
        else:
            y = jax.lax.psum(y, "model")
        return y.astype(xs.dtype).reshape(bl, s, d), aux

    shard = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp, None, None),
                   {"moe_balance": P(), "moe_zloss": P()}),
        check_vma=False,
    )
    y, aux = shard(x, p["router"].astype(jnp.float32),
                   p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared_experts:
        xt = x.reshape(b * s, d)
        hs = jnp.einsum("td,edf->etf", xt, p["shared_gate"])
        hs = jax.nn.silu(hs) * jnp.einsum("td,edf->etf", xt, p["shared_up"])
        y = y + jnp.einsum("etf,efd->td", hs, p["shared_down"]) \
            .astype(x.dtype).reshape(b, s, d)
    return y, aux


def apply_moe_a2a(p: Params, cfg: ModelConfig, x: jax.Array
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """All-to-all expert parallelism (the §Perf upgrade over the psum EP).

    Tokens stay sharded over EVERY mesh axis (batch over dp, sequence over
    'model'); each device routes only its own t_loc tokens.  Dispatch sends
    each token to the model-shard owning its expert via one all_to_all,
    expert FFNs run on (E/M, M*cap) blocks, and a second all_to_all returns
    finished outputs to the token's home device -- no psum, no all-gather
    of the token set.  Wire per MoE layer ~= 2 * t_loc*k*cf*d bytes versus
    the psum path's full-token all-gather + 2x f32 combine.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.api import current_mesh

    mesh = current_mesh()
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    dp = _dp_axes(mesh)
    n_model = mesh.shape["model"]
    e_local = e // n_model

    def local_fn(xs, router, wg, wu, wd):
        bl, sl = xs.shape[0], xs.shape[1]
        t = bl * sl
        xt = xs.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router           # (t, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        def gmean(v):
            for ax in dp + ("model",):
                v = jax.lax.pmean(v, ax)
            return v

        me = gmean(probs.mean(axis=0))
        ce = gmean(jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)]
                   .add(1.0 / (t * k)))
        zloss = gmean((jax.nn.logsumexp(logits, -1) ** 2).mean())
        aux = {"moe_balance": e * jnp.sum(me * ce) * m.aux_loss_weight,
               "moe_zloss": zloss * m.router_z_loss}

        # local restructure: sort MY slots by (global) expert id
        cap = max(int(-(-t * k // e) * m.capacity_factor), 1)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(flat_e)
        se_, sw_, st_ = flat_e[order], flat_w[order], flat_tok[order]
        pos_in_e = jnp.arange(t * k) - jnp.searchsorted(se_, se_, "left")
        keep = pos_in_e < cap
        slot = jnp.where(keep, se_ * cap + pos_in_e, e * cap)

        send = jnp.zeros((e * cap + 1, d), xs.dtype) \
            .at[slot].set(jnp.take(xt, st_, axis=0))[:-1]
        send = send.reshape(n_model, e_local * cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv[src] = tokens from device `src` for MY experts:
        # (M, e_local, cap, d) -> (e_local, M*cap, d)
        gx = recv.reshape(n_model, e_local, cap, d) \
            .transpose(1, 0, 2, 3).reshape(e_local, n_model * cap, d)
        h = jnp.einsum("ecd,edf->ecf", gx, wg)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", gx, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        # back to (M, e_local*cap, d) source-major, return home
        out = out.reshape(e_local, n_model, cap, d) \
            .transpose(1, 0, 2, 3).reshape(n_model, e_local * cap, d)
        back = jax.lax.all_to_all(out, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # back[j] = outputs from expert-shard j for MY tokens, laid out in
        # global-expert-major order == the `slot` indexing above
        back = back.reshape(e * cap, d)
        gathered = jnp.where(
            keep[:, None],
            jnp.take(back, jnp.minimum(slot, e * cap - 1), axis=0), 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[st_].add(
            gathered.astype(jnp.float32) * sw_[:, None])
        return y.astype(xs.dtype).reshape(bl, sl, d), aux

    shard = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp, "model", None),
                   {"moe_balance": P(), "moe_zloss": P()}),
        check_vma=False,
    )
    y, aux = shard(x, p["router"].astype(jnp.float32),
                   p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared_experts:
        xt = x.reshape(b * s, d)
        hs = jnp.einsum("td,edf->etf", xt, p["shared_gate"])
        hs = jax.nn.silu(hs) * jnp.einsum("td,edf->etf", xt, p["shared_up"])
        y = y + jnp.einsum("etf,efd->td", hs, p["shared_down"]) \
            .astype(x.dtype).reshape(b, s, d)
    return y, aux


def apply_moe_decode(p: Params, cfg: ModelConfig, x: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Weight-stationary MoE for decode-sized token counts (§Perf cell 3).

    The train-time EP paths let GSPMD all-gather the FSDP(d)-shard of each
    expert's weights -- 235 MB f32 per weight per layer to multiply a
    handful of tokens.  Here the weights never move: they enter shard_map
    in their native P('model', 'data') placement; each (expert-shard,
    d-shard) device computes a partial GEMM on its d-slice and the psum
    runs over ACTIVATIONS (E/M * cap * ff floats -- kilobytes at decode
    batch sizes).  Wire per layer drops ~4000x for long_500k.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.api import current_mesh

    mesh = current_mesh()
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    dp = _dp_axes(mesh)
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]
    e_local = e // n_model
    d_local = d // n_data

    def local_fn(xs, router, wg, wu, wd):
        # xs is the FULL (replicated) token set: at decode sizes it is a
        # few MB, and replicating it is what lets the d-contraction split
        # over 'data' (sharding batch over 'data' too would make the
        # activation psum mix different tokens' partial slices).
        bl = xs.shape[0]
        t = bl * s
        xt = xs.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        aux = {"moe_balance": jnp.float32(0.0),
               "moe_zloss": jnp.float32(0.0)}   # no aux losses at serve time

        cap = max(int(-(-t * k // e) * m.capacity_factor), 1)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(flat_e)
        se_, sw_, st_ = flat_e[order], flat_w[order], flat_tok[order]
        pos_in_e = jnp.arange(t * k) - jnp.searchsorted(se_, se_, "left")
        j = jax.lax.axis_index("model")
        le = se_ - j * e_local
        in_range = (le >= 0) & (le < e_local) & (pos_in_e < cap)
        slot = jnp.where(in_range, le * cap + pos_in_e, e_local * cap)
        tok_buf = jnp.zeros((e_local * cap + 1,), jnp.int32) \
            .at[slot].set(st_.astype(jnp.int32))[:-1]
        wgt_buf = jnp.zeros((e_local * cap + 1,), jnp.float32) \
            .at[slot].set(jnp.where(in_range, sw_, 0.0))[:-1]

        gx = jnp.take(xt, tok_buf, axis=0)             # (E/M*cap, d)
        i = jax.lax.axis_index("data")
        gxs = jax.lax.dynamic_slice_in_dim(gx, i * d_local, d_local, 1) \
            .reshape(e_local, cap, d_local)
        # f32 partials: the d-contraction is split across 'data' shards, so
        # accumulate & reduce in f32 (the activation psums are kilobytes)
        hg = jax.lax.psum(jnp.einsum(
            "ecd,edf->ecf", gxs, wg,
            preferred_element_type=jnp.float32), "data")
        hu = jax.lax.psum(jnp.einsum(
            "ecd,edf->ecf", gxs, wu,
            preferred_element_type=jnp.float32), "data")
        hmid = jax.nn.silu(hg) * hu                    # (E/M, cap, ff) f32
        out_p = jnp.einsum("ecf,efd->ecd", hmid, wd)   # (E/M, cap, d/D)
        out = jax.lax.all_gather(out_p, "data", axis=2, tiled=True)
        out = out.reshape(e_local * cap, d)
        y = jnp.zeros((t, d), jnp.float32).at[tok_buf].add(
            out.astype(jnp.float32) * wgt_buf[:, None])
        y = jax.lax.psum(y.astype(jnp.bfloat16), "model")
        return y.astype(xs.dtype).reshape(bl, s, d), aux

    shard = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(P(None, None, None),
                   {"moe_balance": P(), "moe_zloss": P()}),
        check_vma=False,
    )
    y, aux = shard(x, p["router"].astype(jnp.float32),
                   p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared_experts:
        xt = x.reshape(b * s, d)
        hs = jnp.einsum("td,edf->etf", xt, p["shared_gate"])
        hs = jax.nn.silu(hs) * jnp.einsum("td,edf->etf", xt, p["shared_up"])
        y = y + jnp.einsum("etf,efd->td", hs, p["shared_down"]) \
            .astype(x.dtype).reshape(b, s, d)
    return y, aux


def apply_moe_auto(p: Params, cfg: ModelConfig, x: jax.Array
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Route to the shard_map EP path when a model-axis mesh is active and
    the expert count divides it; otherwise the global reference path."""
    from repro.distributed.api import current_mesh

    from . import tuning

    mesh = current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.moe.n_experts % mesh.shape["model"] != 0):
        return apply_moe(p, cfg, x)
    # decode (one token per slot): weight-stationary path -- needs no
    # batch divisibility because the token set is replicated
    if (tuning.moe_decode_weight_stationary and x.shape[1] == 1
            and "data" in mesh.axis_names
            and cfg.d_model % mesh.shape["data"] == 0):
        return apply_moe_decode(p, cfg, x)
    if x.shape[0] % _dp_size(mesh) != 0:
        return apply_moe(p, cfg, x)
    if tuning.moe_all_to_all and x.shape[1] % mesh.shape["model"] == 0:
        return apply_moe_a2a(p, cfg, x)
    return apply_moe_sharded(p, cfg, x)


def _dp_size(mesh) -> int:
    n = mesh.shape.get("data", 1)
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def dispatch_structure_demo(top_e: jnp.ndarray, n_experts: int):
    """Build the (T, E) assignment matrix before/after sorting as CSR so
    core.structure.analyze can quantify the restructuring (used by examples
    and tests)."""
    import numpy as np

    from repro.core.formats import CSR

    t, k = top_e.shape
    rows = np.repeat(np.arange(t), k)
    cols = np.asarray(top_e).reshape(-1)
    vals = np.ones(t * k, np.float32)
    unsorted = CSR.from_coo(rows, cols, vals, t, n_experts)
    order = np.argsort(cols, kind="stable")
    sorted_m = CSR.from_coo(np.arange(t * k), cols[order], vals, t * k,
                            n_experts)
    return unsorted, sorted_m
