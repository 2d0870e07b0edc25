"""Model assembly for every assigned architecture family.

One generic LM covering dense / MoE / MLA attention, Mamba2 (SSM),
Zamba2-style hybrid (mamba backbone + weight-tied shared attention block),
Llama-3.2-Vision-style gated cross-attention layers, and a Seamless-style
encoder-decoder.  Layers are stacked with ``lax.scan`` (keeps HLO size O(1)
in depth — critical for 80-100 layer dry-runs) and rematerialized per layer
according to ``cfg.remat``.

Caches: per-layer tensors are stacked on a leading layer axis and carried as
scan xs/ys; the decode position lives in a single global ``length`` scalar
injected into each layer's view inside the scan body.

API (used by launch/dryrun, launch/train, serve/engine):

* ``init(key)``                       -> (params, specs)
* ``loss_fn(params, batch)``          -> (loss, metrics)
* ``prefill(params, batch)``          -> (last_logits, cache)
* ``decode_step(params, cache, tok)`` -> (last_logits, cache)
* ``input_specs(shape)`` / ``cache_specs(shape)`` live in registry.py
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .attention import (
    cross_attn_apply,
    gqa_apply,
    init_cross_attn,
    init_gqa,
    init_mla,
    mla_apply,
)
from .common import DP, TP, dense_init, dtype_of, embed_init, rmsnorm, rmsnorm_init
from .moe import init_moe, moe_apply_reference, moe_apply_sharded
from .ssm import init_mamba2, mamba2_apply

__all__ = ["LMModel", "init_mlp", "mlp_apply"]


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, ff: int, dtype):
    ks = jax.random.split(key, 3)
    pi, si = dense_init(ks[0], d, ff, dtype, in_axis=DP)
    pg, sg = dense_init(ks[1], d, ff, dtype, in_axis=DP)
    po, so = dense_init(ks[2], ff, d, dtype, in_axis=TP, out_axis=DP)
    return {"wi": pi, "wg": pg, "wo": po}, {"wi": si, "wg": sg, "wo": so}


def mlp_apply(p, x):
    a = jnp.einsum("bsd,df->bsf", x, p["wi"]["w"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"]["w"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * a, p["wo"]["w"])


# ---------------------------------------------------------------------------
# layer inits
# ---------------------------------------------------------------------------


def _init_dense_layer(key, cfg, dtype, cross: bool = False, use_mla: bool = False):
    ks = jax.random.split(key, 2)
    n1, s1 = rmsnorm_init(cfg.d_model, dtype)
    n2, s2 = rmsnorm_init(cfg.d_model, dtype)
    if cross:
        pa, sa = init_cross_attn(ks[0], cfg, dtype)
    elif use_mla:
        pa, sa = init_mla(ks[0], cfg, dtype)
    else:
        pa, sa = init_gqa(ks[0], cfg, dtype)
    pm, sm = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return (
        {"n1": n1, "attn": pa, "n2": n2, "mlp": pm},
        {"n1": s1, "attn": sa, "n2": s2, "mlp": sm},
    )


def _init_moe_layer(key, cfg, dtype, data_size: int):
    ks = jax.random.split(key, 2)
    n1, s1 = rmsnorm_init(cfg.d_model, dtype)
    n2, s2 = rmsnorm_init(cfg.d_model, dtype)
    use_mla = cfg.mla is not None
    pa, sa = init_mla(ks[0], cfg, dtype) if use_mla else init_gqa(ks[0], cfg, dtype)
    pm, sm = init_moe(ks[1], cfg, dtype, data_size)
    return (
        {"n1": n1, "attn": pa, "n2": n2, "moe": pm},
        {"n1": s1, "attn": sa, "n2": s2, "moe": sm},
    )


def _init_ssm_layer(key, cfg, dtype):
    n1, s1 = rmsnorm_init(cfg.d_model, dtype)
    pm, sm = init_mamba2(key, cfg, dtype)
    return {"n1": n1, "mamba": pm}, {"n1": s1, "mamba": sm}


def _stack(init_one, key, n):
    keys = jax.random.split(key, n)
    _, sp = init_one(keys[0])
    ps = jax.vmap(lambda k: init_one(k)[0])(keys)
    sp = jax.tree.map(lambda s: P(None, *s), sp, is_leaf=lambda s: isinstance(s, P))
    return ps, sp


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LMModel:
    cfg: ModelConfig
    data_size: int = 16  # data-axis extent (for MoE expert slotting)
    use_sharded_moe: bool = False  # shard_map EP; False = reference (CPU tests)
    batch_axes: Tuple[str, ...] = ("data",)
    mesh: Optional[object] = None

    # ------------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        dtype = dtype_of(cfg)
        ks = jax.random.split(key, 8)
        params: Dict = {}
        specs: Dict = {}
        pe, se = embed_init(ks[0], cfg.vocab, cfg.d_model, dtype)
        params["embed"], specs["embed"] = pe, se
        if not cfg.tie_embeddings:
            pu, su = dense_init(ks[1], cfg.d_model, cfg.vocab, dtype, in_axis=DP)
            params["unembed"], specs["unembed"] = pu, su
        nf, sf = rmsnorm_init(cfg.d_model, dtype)
        params["final_norm"], specs["final_norm"] = nf, sf

        fam = cfg.family
        if fam in ("dense", "moe"):
            use_mla = cfg.mla is not None
            if fam == "moe":
                init_one = lambda k: _init_moe_layer(k, cfg, dtype, self.data_size)
            else:
                init_one = lambda k: _init_dense_layer(k, cfg, dtype, use_mla=use_mla)
            params["layers"], specs["layers"] = _stack(init_one, ks[2], cfg.n_layers)
        elif fam == "ssm":
            params["layers"], specs["layers"] = _stack(
                lambda k: _init_ssm_layer(k, cfg, dtype), ks[2], cfg.n_layers)
        elif fam == "hybrid":
            params["layers"], specs["layers"] = _stack(
                lambda k: _init_ssm_layer(k, cfg, dtype), ks[2], cfg.n_layers)
            params["shared"], specs["shared"] = _init_dense_layer(ks[3], cfg, dtype)
        elif fam == "vlm":
            period = cfg.cross_attn_every
            n_cross = cfg.n_layers // period
            n_self_per = period - 1
            p_self, s_self = _stack(lambda k: _init_dense_layer(k, cfg, dtype),
                                    ks[2], n_cross * n_self_per)
            params["self_layers"] = jax.tree.map(
                lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]), p_self)
            specs["self_layers"] = jax.tree.map(
                lambda s: P(None, *s), s_self, is_leaf=lambda s: isinstance(s, P))
            params["cross_layers"], specs["cross_layers"] = _stack(
                lambda k: _init_dense_layer(k, cfg, dtype, cross=True), ks[3], n_cross)
            pv, sv = dense_init(ks[4], cfg.d_vision, cfg.d_model, dtype,
                                in_axis=None, out_axis=None)
            params["vis_proj"], specs["vis_proj"] = pv, sv
        elif fam == "audio":
            params["enc_layers"], specs["enc_layers"] = _stack(
                lambda k: _init_dense_layer(k, cfg, dtype), ks[2], cfg.n_enc_layers)

            def init_dec(k):
                k1, k2 = jax.random.split(k)
                p1, s1 = _init_dense_layer(k1, cfg, dtype)
                pc, sc = init_cross_attn(k2, cfg, dtype)
                nc, snc = rmsnorm_init(cfg.d_model, dtype)
                p1["cross"], s1["cross"] = pc, sc
                p1["nc"], s1["nc"] = nc, snc
                return p1, s1

            params["dec_layers"], specs["dec_layers"] = _stack(init_dec, ks[3], cfg.n_dec_layers)
            pa, sa = dense_init(ks[4], cfg.d_audio, cfg.d_model, dtype,
                                in_axis=None, out_axis=None)
            params["audio_proj"], specs["audio_proj"] = pa, sa
        else:  # pragma: no cover
            raise ValueError(fam)
        return params, specs

    # ------------------------------------------------------------------
    # building blocks
    # ------------------------------------------------------------------
    def _attn(self, p, x, positions, mode, cache):
        if self.cfg.mla is not None:
            return mla_apply(p, self.cfg, x, positions, mode, cache)
        return gqa_apply(p, self.cfg, x, positions, mode, cache)

    def _moe_ffn(self, p, x):
        if not self.use_sharded_moe:
            return moe_apply_reference(p, self.cfg, x)
        bspec = P(self.batch_axes, None, None)
        pspec = {
            "router": {"w": P(None, None)},
            "wi": P("data", None, "model"),
            "wg": P("data", None, "model"),
            "wo": P("data", "model", None),
        }
        if "shared" in p:
            pspec["shared"] = {
                "wi": {"w": P(None, "model")},
                "wg": {"w": P(None, "model")},
                "wo": {"w": P("model", None)},
            }
        return jax.shard_map(
            lambda pp, xx: moe_apply_sharded(pp, self.cfg, xx),
            mesh=self.mesh,
            in_specs=(pspec, bspec),
            out_specs=(bspec, {"aux": P(), "dropped": P()}),
            check_vma=False,
        )(p, x)

    def _dense_layer_apply(self, p, x, positions, mode, cache):
        cfg = self.cfg
        h, nc = self._attn(p["attn"], rmsnorm(x, p["n1"]["scale"], cfg.norm_eps),
                           positions, mode, cache)
        x = x + h
        x = x + mlp_apply(p["mlp"], rmsnorm(x, p["n2"]["scale"], cfg.norm_eps))
        return x, nc, jnp.float32(0)

    def _moe_layer_apply(self, p, x, positions, mode, cache):
        cfg = self.cfg
        h, nc = self._attn(p["attn"], rmsnorm(x, p["n1"]["scale"], cfg.norm_eps),
                           positions, mode, cache)
        x = x + h
        m, aux = self._moe_ffn(p["moe"], rmsnorm(x, p["n2"]["scale"], cfg.norm_eps))
        return x + m, nc, aux["aux"]

    def _ssm_layer_apply(self, p, x, mode, cache):
        cfg = self.cfg
        h, nc = mamba2_apply(p["mamba"], cfg,
                             rmsnorm(x, p["n1"]["scale"], cfg.norm_eps), mode, cache)
        return x + h, nc

    def _remat(self, fn, mode):
        if mode == "train" and self.cfg.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.cfg.remat == "dots" else None)
            return jax.checkpoint(fn, policy=policy)
        return fn

    def _embed(self, params, tokens):
        y = jnp.take(params["embed"]["w"], tokens, axis=0)
        if self.mesh is not None:
            y = jax.lax.with_sharding_constraint(y, P(self.batch_axes, None, None))
        return y

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, params["embed"]["w"])
        return jnp.einsum("bsd,dv->bsv", x, params["unembed"]["w"])

    # scan cache helpers ---------------------------------------------------
    @staticmethod
    def _with_len(lc, glen):
        """Inject the global decode position into a per-layer cache view."""
        if lc is None or glen is None:
            return lc
        out = dict(lc)
        out["length"] = glen
        return out

    def _xs_caches(self, caches_layers, n_layers, mode):
        if mode in ("train", "encode") or caches_layers is None:
            return jnp.zeros((n_layers, 1), jnp.int32)  # dummy xs
        return caches_layers

    # ------------------------------------------------------------------
    # backbones
    # ------------------------------------------------------------------
    def _decoder_stack(self, params_layers, x, positions, mode, caches, apply3):
        """Homogeneous scan.  caches: {"layers": stacked, "length": scalar}|None.
        apply3(p, x, positions, mode, cache) -> (x, new_cache, aux)."""
        cfg = self.cfg
        glen = caches["length"] if (caches is not None and mode == "decode") else None
        n_layers = jax.tree.leaves(params_layers)[0].shape[0]

        def body(carry, xs):
            lp, lc = xs
            cache_in = self._with_len(lc, glen) if mode == "decode" else None
            fn = self._remat(
                lambda q, qp, qc: apply3(qp, q, positions, mode, qc), mode)
            xx, nc, aux = fn(carry, lp, cache_in)
            if nc is None:
                nc = jnp.int32(0)  # dummy ys
            return xx, (nc, aux)

        xs_c = self._xs_caches(caches["layers"] if caches else None, n_layers, mode)
        x, (ncaches, auxs) = jax.lax.scan(body, x, (params_layers, xs_c))
        new_caches = None
        if mode == "prefill":
            new_caches = {"layers": ncaches, "length": jnp.int32(x.shape[1])}
        elif mode == "decode":
            new_caches = {"layers": ncaches, "length": caches["length"] + 1}
        return x, new_caches, auxs.sum()

    # ------------------------------------------------------------------
    def _full_forward(self, params, batch, mode, caches=None):
        cfg = self.cfg
        fam = cfg.family
        if fam == "vlm":
            return self._vlm_forward(params, batch, mode, caches)
        if fam == "audio":
            return self._audio_forward(params, batch, mode, caches)

        tokens = batch["tokens"]
        B, S = tokens.shape
        if mode == "decode":
            positions = jnp.broadcast_to(caches["length"], (B, 1))
        else:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = self._embed(params, tokens)

        if fam in ("dense", "moe"):
            apply3 = self._moe_layer_apply if fam == "moe" else self._dense_layer_apply
            x, ncaches, aux = self._decoder_stack(
                params["layers"], x, positions, mode, caches, apply3)
        elif fam == "ssm":
            apply3 = lambda p, q, pos, m, c: (*self._ssm_layer_apply(p, q, m, c), jnp.float32(0))
            x, ncaches, aux = self._decoder_stack(
                params["layers"], x, positions, mode, caches, apply3)
        elif fam == "hybrid":
            x, ncaches, aux = self._hybrid_backbone(params, x, positions, mode, caches)
        else:  # pragma: no cover
            raise ValueError(fam)

        x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = self._logits(params, x)
        return logits, ncaches, aux

    # -- hybrid (zamba2) ----------------------------------------------------
    def _hybrid_backbone(self, params, x, positions, mode, caches):
        cfg = self.cfg
        period = cfg.shared_attn_every
        n_shared = cfg.n_layers // period
        head = n_shared * period
        tail = cfg.n_layers - head
        glen = caches["length"] if (caches is not None and mode == "decode") else None

        mp_all = params["layers"]
        mp_head = jax.tree.map(lambda a: a[:head].reshape(n_shared, period, *a.shape[1:]),
                               mp_all)
        mp_tail = jax.tree.map(lambda a: a[head:], mp_all)

        mc_all = caches["mamba"] if caches is not None and mode != "prefill" else None
        if mc_all is not None:
            mc_head = jax.tree.map(
                lambda a: a[:head].reshape(n_shared, period, *a.shape[1:]), mc_all)
            mc_tail = jax.tree.map(lambda a: a[head:], mc_all)
        else:
            mc_head = jnp.zeros((n_shared, period, 1), jnp.int32)
            mc_tail = jnp.zeros((max(tail, 1), 1), jnp.int32)
        sc_all = (caches["shared"] if caches is not None and mode != "prefill"
                  else jnp.zeros((n_shared, 1), jnp.int32))

        def mamba_fn(q, qp, qc):
            cache_in = self._with_len(qc, glen) if mode == "decode" else None
            return self._ssm_layer_apply(qp, q, mode, cache_in)

        def super_body(carry, xs):
            xx = carry
            mp, mc, sc = xs

            def inner(c2, xs2):
                lp, lc = xs2
                fn = self._remat(mamba_fn, mode)
                yy, ncc = fn(c2, lp, lc)
                return yy, (ncc if ncc is not None else jnp.int32(0))

            xx, nmc = jax.lax.scan(inner, xx, (mp, mc))
            cache_in = self._with_len(sc, glen) if mode == "decode" else None
            fn = self._remat(
                lambda q, qp, qc: self._dense_layer_apply(qp, q, positions, mode, qc),
                mode)
            xx, nsc, _ = fn(xx, params["shared"], cache_in)
            return xx, (nmc, nsc if nsc is not None else jnp.int32(0))

        x, (nmc_head, nsc) = jax.lax.scan(super_body, x, (mp_head, mc_head, sc_all))

        if tail:
            def tail_body(c2, xs2):
                lp, lc = xs2
                fn = self._remat(mamba_fn, mode)
                yy, ncc = fn(c2, lp, lc)
                return yy, (ncc if ncc is not None else jnp.int32(0))
            x, nmc_tail = jax.lax.scan(tail_body, x, (mp_tail, mc_tail))

        if mode == "train":
            return x, None, jnp.float32(0)
        nmc = jax.tree.map(lambda h: h.reshape(head, *h.shape[2:]), nmc_head)
        if tail:
            nmc = jax.tree.map(lambda h, t: jnp.concatenate([h, t], 0), nmc, nmc_tail)
        length = (caches["length"] + 1) if mode == "decode" else jnp.int32(x.shape[1])
        return x, {"mamba": nmc, "shared": nsc, "length": length}, jnp.float32(0)

    # -- vlm ----------------------------------------------------------------
    def _vlm_forward(self, params, batch, mode, caches=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        glen = caches["length"] if (caches is not None and mode == "decode") else None
        if mode == "decode":
            positions = jnp.broadcast_to(caches["length"], (B, 1))
            vis = None
        else:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            vis = jnp.einsum("bnd,df->bnf", batch["vision_embeds"],
                             params["vis_proj"]["w"])
        x = self._embed(params, tokens)
        n_cross = cfg.n_layers // cfg.cross_attn_every

        scs = (caches["self"] if caches is not None and mode == "decode"
               else jnp.zeros((n_cross, cfg.cross_attn_every - 1, 1), jnp.int32))
        ccs = (caches["cross"] if caches is not None and mode == "decode"
               else jnp.zeros((n_cross, 1), jnp.int32))

        def super_body(carry, xs):
            xx = carry
            sp, cp, sc, cc = xs

            def inner(c2, xs2):
                lp, lc = xs2
                cache_in = self._with_len(lc, glen) if mode == "decode" else None
                fn = self._remat(
                    lambda q, qp, qc: self._dense_layer_apply(qp, q, positions, mode, qc),
                    mode)
                yy, ncc, _ = fn(c2, lp, cache_in)
                return yy, (ncc if ncc is not None else jnp.int32(0))

            xx, nsc = jax.lax.scan(inner, xx, (sp, sc))

            def cross_fn(q, qp, qc):
                h, ncc = cross_attn_apply(qp["attn"], cfg,
                                          rmsnorm(q, qp["n1"]["scale"], cfg.norm_eps),
                                          vis, mode, qc)
                q = q + h
                q = q + mlp_apply(qp["mlp"], rmsnorm(q, qp["n2"]["scale"], cfg.norm_eps))
                return q, ncc
            fn = self._remat(cross_fn, mode)
            cc_in = cc if mode == "decode" else None
            xx, ncc = fn(xx, cp, cc_in)
            return xx, (nsc, ncc if ncc is not None else jnp.int32(0))

        x, (nsc, ncc) = jax.lax.scan(
            super_body, x, (params["self_layers"], params["cross_layers"], scs, ccs))
        x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = self._logits(params, x)
        if mode == "train":
            return logits, None, jnp.float32(0)
        length = (caches["length"] + 1) if mode == "decode" else jnp.int32(S)
        return logits, {"self": nsc, "cross": ncc, "length": length}, jnp.float32(0)

    # -- audio (enc-dec) -----------------------------------------------------
    def _audio_forward(self, params, batch, mode, caches=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, St = tokens.shape
        glen = caches["length"] if (caches is not None and mode == "decode") else None

        if mode == "decode":
            positions = jnp.broadcast_to(caches["length"], (B, 1))
            enc_out = None
        else:
            frames = batch["frames"]
            Sa = frames.shape[1]
            h = jnp.einsum("bsa,ad->bsd", frames, params["audio_proj"]["w"])
            pos_enc = jnp.broadcast_to(jnp.arange(Sa), (B, Sa))

            def enc_body(c2, lp):
                def enc_fn(q, qp):
                    a, _ = gqa_apply(qp["attn"], cfg,
                                     rmsnorm(q, qp["n1"]["scale"], cfg.norm_eps),
                                     pos_enc, "encode", None)
                    q = q + a
                    q = q + mlp_apply(qp["mlp"], rmsnorm(q, qp["n2"]["scale"], cfg.norm_eps))
                    return q
                fn = self._remat(enc_fn, mode)
                return fn(c2, lp), None

            h, _ = jax.lax.scan(enc_body, h, params["enc_layers"])
            enc_out = h
            positions = jnp.broadcast_to(jnp.arange(St), (B, St))

        x = self._embed(params, tokens)
        n = cfg.n_dec_layers
        scs = (caches["self"] if caches is not None and mode == "decode"
               else jnp.zeros((n, 1), jnp.int32))
        ccs = (caches["cross"] if caches is not None and mode == "decode"
               else jnp.zeros((n, 1), jnp.int32))

        def dec_body(carry, xs):
            lp, lc_self, lc_cross = xs
            cs_in = self._with_len(lc_self, glen) if mode == "decode" else None
            cc_in = lc_cross if mode == "decode" else None

            def dec_fn(q, qp, qcs, qcc):
                a, ncs = gqa_apply(qp["attn"], cfg,
                                   rmsnorm(q, qp["n1"]["scale"], cfg.norm_eps),
                                   positions, mode, qcs)
                q = q + a
                c, ncc = cross_attn_apply(qp["cross"], cfg,
                                          rmsnorm(q, qp["nc"]["scale"], cfg.norm_eps),
                                          enc_out, mode, qcc)
                q = q + c
                q = q + mlp_apply(qp["mlp"], rmsnorm(q, qp["n2"]["scale"], cfg.norm_eps))
                return q, ncs, ncc
            fn = self._remat(dec_fn, mode)
            xx, ncs, ncc = fn(carry, lp, cs_in, cc_in)
            return xx, (ncs if ncs is not None else jnp.int32(0),
                        ncc if ncc is not None else jnp.int32(0))

        x, (nsc, ncc) = jax.lax.scan(dec_body, x, (params["dec_layers"], scs, ccs))
        x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = self._logits(params, x)
        if mode == "train":
            return logits, None, jnp.float32(0)
        length = (caches["length"] + 1) if mode == "decode" else jnp.int32(St)
        return logits, {"self": nsc, "cross": ncc, "length": length}, jnp.float32(0)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def loss_fn(self, params, batch):
        tokens = batch["tokens"]
        inp = {**batch, "tokens": tokens[:, :-1]}
        logits, _, aux = self._full_forward(params, inp, "train")
        targets = tokens[:, 1:]
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = (lse - gold).mean()
        loss = nll + self.cfg.moe.router_aux_coef * aux if self.cfg.moe else nll
        return loss, {"nll": nll, "aux": aux}

    def prefill(self, params, batch):
        logits, caches, _ = self._full_forward(params, batch, "prefill")
        return logits[:, -1], caches

    def decode_step(self, params, caches, tokens):
        logits, ncaches, _ = self._full_forward(params, {"tokens": tokens}, "decode", caches)
        return logits[:, -1], ncaches
