"""Unified Model API: config -> init / loss_fn / prefill / decode_step.

One class drives every assigned architecture family:

  dense | vlm    decoder-only transformer (vlm prepends patch embeddings)
  moe            decoder-only with routed-expert MLPs
  ssm            Mamba1 stack (attention-free)
  hybrid         Mamba2 stack + weight-shared attention block every K layers
  encdec         encoder-decoder (audio frontend stubbed as frame embeddings)

All step functions are pure (params, batch) -> outputs so they can be jitted
under any mesh. `input_specs` returns ShapeDtypeStruct stand-ins for every
input of the train/prefill/decode step of a given shape cell — the dry-run
lowers against these with zero allocation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from .config import ModelConfig, ShapeConfig
from .layers import (dense, dense_init, embed, embedding_init,
                     rmsnorm, rmsnorm_init, unembed)
from .transformer import (ExecConfig, encoder_forward,
                          stack_forward, stack_init)

Params = Any


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def masked_chunked_xent(table: jnp.ndarray, x: jnp.ndarray,
                        labels: jnp.ndarray, compute_dtype,
                        n_chunks: int = 8) -> jnp.ndarray:
    """Cross-entropy over sequence chunks; labels < 0 are ignored.

    Never materializes the full (B,S,V) logits — peak logit memory is
    (B, S/n_chunks, V) inside one scan iteration.
    """
    B, S, _ = x.shape
    if S % n_chunks != 0:
        n_chunks = 1
    tbl = table.astype(compute_dtype)
    xs = x.reshape(B, n_chunks, S // n_chunks, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n_chunks, S // n_chunks).transpose(1, 0, 2)

    def body(carry, xl):
        tot, cnt = carry
        xc, lc = xl
        valid = (lc >= 0)
        lc_safe = jnp.maximum(lc, 0)
        logits = xc.astype(compute_dtype) @ tbl.T
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, lc_safe[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * valid
        return (tot + jnp.sum(nll), cnt + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ls))
    return tot / jnp.maximum(cnt, 1.0)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    ec: ExecConfig = ExecConfig()

    # ------------------------------------------------------------- params

    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = _pdt(cfg)
        k_emb, k_stack, k_front = jax.random.split(key, 3)
        params = {
            "embedding": embedding_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
            "stack": stack_init(k_stack, cfg, dtype),
            "ln_f": rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = embedding_init(
                jax.random.fold_in(k_emb, 1), cfg.vocab_size, cfg.d_model,
                dtype)
        if cfg.frontend:
            params["frontend_proj"] = dense_init(
                k_front, cfg.d_model, cfg.d_model, dtype)
        if cfg.family == "encdec":
            params["ln_enc"] = rmsnorm_init(cfg.d_model, dtype)
        return params

    def head(self, params) -> Params:
        """The output head: its own leaf, or the embedding where tied."""
        return params["embedding" if self.cfg.tie_embeddings else "lm_head"]

    def abstract_params(self) -> Params:
        """ShapeDtypeStruct pytree of the parameters (no allocation)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ forward

    def _embed_inputs(self, params, batch, dt):
        """Token embedding (+frontend overwrite for vlm)."""
        cfg = self.cfg
        x = embed(params["embedding"], batch["tokens"], dt)
        if cfg.family == "vlm" and "frontend_emb" in batch:
            fe = dense(params["frontend_proj"], batch["frontend_emb"], dt)
            nf = fe.shape[1]
            x = jnp.concatenate([fe, x[:, nf:]], axis=1)
        return x

    def forward(self, params, batch):
        """Full-sequence forward -> (hidden (B,S,D), aux): aux["aux"] is
        the router's load-balancing loss (0 without routed experts), and
        aux["expert_rows"] (layers, held experts) the rows each held
        expert computed and aux["expert_full"] (layers,) 1 where a layer
        ran its full-size core (`moe_mlp`'s stats)."""
        cfg, ec = self.cfg, self.ec
        dt = _dt(cfg)
        x = self._embed_inputs(params, batch, dt)
        S = x.shape[1]
        positions = jnp.arange(S)[None, :]
        enc_out = None
        if cfg.family == "encdec":
            fe = dense(params["frontend_proj"], batch["enc_emb"], dt)
            enc_out = encoder_forward(params["stack"], fe, cfg, ec, dt)
            enc_out = rmsnorm(params["ln_enc"], enc_out, cfg.norm_eps)
        h, aux = stack_forward(params["stack"], x, cfg, ec, positions, dt,
                               enc_out=enc_out)
        if cfg.is_moe:
            aux = {"aux": moe_mod.load_balance_loss(
                       aux["counts"], aux["probs"],
                       x.shape[0] * S * cfg.n_layers),
                   "expert_rows": aux["rows"], "expert_full": aux["full"]}
        else:
            aux = {"aux": aux}
        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return h, aux

    def logits(self, params, batch):
        """(B,S,V) logits — small-model/test path only."""
        h, aux = self.forward(params, batch)
        return unembed(self.head(params), h, _dt(self.cfg)), aux

    def loss_fn(self, params, batch):
        """Mean token cross-entropy + the router's load-balancing loss
        times its coefficient. Returns (loss, metrics)."""
        cfg = self.cfg
        h, aux = self.forward(params, batch)
        xent = masked_chunked_xent(self.head(params)["table"], h,
                                   batch["labels"], _dt(cfg),
                                   n_chunks=self.ec.xent_chunks)
        loss = xent + cfg.router_aux_coef * aux["aux"]
        return loss, {"xent": xent, **aux}

    # ------------------------------------------------------- decode state

    def init_decode_state(self, batch: int, max_len: int, *,
                          abstract: bool = False):
        cfg = self.cfg
        dt = _dt(cfg)

        def mk(shape, dtype):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jnp.zeros(shape, dtype)

        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            s = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            return {"k": mk(s, dt), "v": mk(s, dt)}
        if fam == "ssm":
            st = mamba_mod.mamba_init_state(cfg, batch, jnp.float32, abstract)
            return jax.tree.map(
                lambda a: (jax.ShapeDtypeStruct((cfg.n_layers,) + a.shape, a.dtype)
                           if abstract else
                           jnp.zeros((cfg.n_layers,) + a.shape, a.dtype)),
                st)
        if fam == "hybrid":
            G, tail = divmod(cfg.n_layers, cfg.attn_every)
            st = mamba_mod.mamba_init_state(cfg, batch, jnp.float32, abstract)

            def grouped(a, lead):
                shape = lead + a.shape
                return jax.ShapeDtypeStruct(shape, a.dtype) if abstract \
                    else jnp.zeros(shape, a.dtype)

            out = {"mamba": jax.tree.map(
                lambda a: grouped(a, (G, cfg.attn_every)), st)}
            if tail:
                out["tail"] = jax.tree.map(lambda a: grouped(a, (tail,)), st)
            s = (G, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            out["attn"] = {"k": mk(s, dt), "v": mk(s, dt)}
            return out
        if fam == "encdec":
            s = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            sx = (cfg.n_layers, batch, cfg.enc_seq_len, cfg.n_kv_heads,
                  cfg.head_dim)
            return {"k": mk(s, dt), "v": mk(s, dt),
                    "cross_k": mk(sx, dt), "cross_v": mk(sx, dt)}
        raise ValueError(fam)

    # ------------------------------------------------------------ prefill

    def prefill(self, params, batch, max_len: int):
        """Process a prompt; returns (last-position logits, decode state).

        The returned KV caches are padded to max_len so decode can continue
        in place.
        """
        cfg, ec = self.cfg, self.ec
        dt = _dt(cfg)
        fam = cfg.family
        x = self._embed_inputs(params, batch, dt)
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]

        def pad_cache(c):   # (L,B,S,H,hd) -> (L,B,max_len,H,hd)
            pad = max_len - c.shape[2]
            if pad <= 0:
                return c
            return jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

        if fam in ("dense", "moe", "vlm"):
            def body(h, lp):
                hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
                o, k, v = attn_mod.attention_with_kv(
                    lp["attn"], hn, cfg, positions=positions,
                    impl=ec.attn_impl, compute_dtype=dt)
                h = h + o
                hn = rmsnorm(lp["ln2"], h, cfg.norm_eps)
                if fam == "moe":
                    y, _ = moe_mod.moe_mlp(lp["moe"], hn, cfg, dt)
                else:
                    from .layers import mlp
                    y = mlp(lp["mlp"], hn, dt)
                return h + y, (k.astype(dt), v.astype(dt))

            x, (ks, vs) = jax.lax.scan(body, x, params["stack"]["layers"])
            state = {"k": pad_cache(ks), "v": pad_cache(vs)}

        elif fam == "ssm":
            def body(h, lp):
                hn = rmsnorm(lp["ln"], h, cfg.norm_eps)
                y, st = mamba_mod.mamba_forward_with_state(lp["mamba"], hn,
                                                           cfg, dt)
                return h + y, st

            x, states = jax.lax.scan(body, x, params["stack"]["layers"])
            state = states

        elif fam == "hybrid":
            shared = params["stack"]["shared"]

            def group_body(h, xs):
                gp, = xs

                def inner(hh, lp):
                    hn = rmsnorm(lp["ln"], hh, cfg.norm_eps)
                    y, st = mamba_mod.mamba_forward_with_state(
                        lp["mamba"], hn, cfg, dt)
                    return hh + y, st

                h, sts = jax.lax.scan(inner, h, gp)
                hn = rmsnorm(shared["ln1"], h, cfg.norm_eps)
                o, k, v = attn_mod.attention_with_kv(
                    shared["attn"], hn, cfg, positions=positions,
                    impl=ec.attn_impl, compute_dtype=dt)
                h = h + o
                from .layers import mlp
                h = h + mlp(shared["mlp"],
                            rmsnorm(shared["ln2"], h, cfg.norm_eps), dt)
                return h, (sts, k.astype(dt), v.astype(dt))

            x, (msts, ks, vs) = jax.lax.scan(
                group_body, x, (params["stack"]["layers"],))
            state = {"mamba": msts, "attn": {"k": pad_cache(ks),
                                             "v": pad_cache(vs)}}
            if "tail" in params["stack"]:
                def tail_body(h, lp):
                    hn = rmsnorm(lp["ln"], h, cfg.norm_eps)
                    y, st = mamba_mod.mamba_forward_with_state(
                        lp["mamba"], hn, cfg, dt)
                    return h + y, st
                x, tsts = jax.lax.scan(tail_body, x,
                                       params["stack"]["tail"])
                state["tail"] = tsts

        elif fam == "encdec":
            fe = dense(params["frontend_proj"], batch["enc_emb"], dt)
            enc_out = encoder_forward(params["stack"], fe, cfg, ec, dt)
            enc_out = rmsnorm(params["ln_enc"], enc_out, cfg.norm_eps)

            def body(h, lp):
                hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
                o, k, v = attn_mod.attention_with_kv(
                    lp["attn"], hn, cfg, positions=positions,
                    impl=ec.attn_impl, compute_dtype=dt)
                h = h + o
                hx = rmsnorm(lp["ln_x"], h, cfg.norm_eps)
                h = h + attn_mod.attention(lp["cross"], hx, cfg,
                                           kv_input=enc_out,
                                           impl=ec.attn_impl,
                                           compute_dtype=dt)
                ck, cv = attn_mod.project_cross_kv(lp["cross"], enc_out,
                                                   cfg, dt)
                from .layers import mlp
                h = h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h, cfg.norm_eps), dt)
                return h, (k.astype(dt), v.astype(dt),
                           ck.astype(dt), cv.astype(dt))

            x, (ks, vs, cks, cvs) = jax.lax.scan(body, x,
                                                 params["stack"]["layers"])
            state = {"k": pad_cache(ks), "v": pad_cache(vs),
                     "cross_k": cks, "cross_v": cvs}
        else:
            raise ValueError(fam)

        h = rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
        logits = unembed(self.head(params), h, dt)
        return logits, state

    # -------------------------------------------------------- decode step

    def decode_step(self, params, token, state, pos):
        """One-token decode. token: (B,1) int32; pos: scalar int32, or a
        (B,) int32 vector of per-row positions (the serving engine's
        continuous batching — see models.attention.decode_attention).

        Returns (logits (B,1,V), new_state). The KV/SSM state threading is
        what the serve_step lowers for the decode_* roofline cells.
        """
        cfg = self.cfg
        dt = _dt(cfg)
        fam = cfg.family
        x = embed(params["embedding"], token, dt)

        if fam in ("dense", "moe", "vlm"):
            def body(h, xs):
                lp, ck, cv = xs
                hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
                o, ck, cv = attn_mod.decode_attention(
                    lp["attn"], hn, cfg, cache_k=ck, cache_v=cv, pos=pos,
                    compute_dtype=dt)
                h = h + o
                hn = rmsnorm(lp["ln2"], h, cfg.norm_eps)
                if fam == "moe":
                    y, _ = moe_mod.moe_mlp(lp["moe"], hn, cfg, dt)
                else:
                    from .layers import mlp
                    y = mlp(lp["mlp"], hn, dt)
                return h + y, (ck, cv)

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["stack"]["layers"], state["k"], state["v"]))
            new_state = {"k": ks, "v": vs}

        elif fam == "ssm":
            def body(h, xs):
                lp, st = xs
                hn = rmsnorm(lp["ln"], h, cfg.norm_eps)
                y, st = mamba_mod.mamba_step(lp["mamba"], hn, st, cfg, dt)
                return h + y, st

            x, new_state = jax.lax.scan(
                body, x, (params["stack"]["layers"], state))

        elif fam == "hybrid":
            shared = params["stack"]["shared"]

            def group_body(h, xs):
                gp, mst, ck, cv = xs

                def inner(hh, ys):
                    lp, st = ys
                    hn = rmsnorm(lp["ln"], hh, cfg.norm_eps)
                    y, st = mamba_mod.mamba_step(lp["mamba"], hn, st, cfg, dt)
                    return hh + y, st

                h, msts = jax.lax.scan(inner, h, (gp, mst))
                hn = rmsnorm(shared["ln1"], h, cfg.norm_eps)
                o, ck, cv = attn_mod.decode_attention(
                    shared["attn"], hn, cfg, cache_k=ck, cache_v=cv, pos=pos,
                    compute_dtype=dt)
                h = h + o
                from .layers import mlp
                h = h + mlp(shared["mlp"],
                            rmsnorm(shared["ln2"], h, cfg.norm_eps), dt)
                return h, (msts, ck, cv)

            x, (msts, ks, vs) = jax.lax.scan(
                group_body, x,
                (params["stack"]["layers"], state["mamba"],
                 state["attn"]["k"], state["attn"]["v"]))
            new_state = {"mamba": msts, "attn": {"k": ks, "v": vs}}
            if "tail" in state:
                def tail_body(h, xs):
                    lp, st = xs
                    hn = rmsnorm(lp["ln"], h, cfg.norm_eps)
                    y, st = mamba_mod.mamba_step(lp["mamba"], hn, st, cfg, dt)
                    return h + y, st
                x, tsts = jax.lax.scan(
                    tail_body, x, (params["stack"]["tail"], state["tail"]))
                new_state["tail"] = tsts

        elif fam == "encdec":
            def body(h, xs):
                lp, ck, cv, xk, xv = xs
                hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
                o, ck, cv = attn_mod.decode_attention(
                    lp["attn"], hn, cfg, cache_k=ck, cache_v=cv, pos=pos,
                    compute_dtype=dt)
                h = h + o
                hx = rmsnorm(lp["ln_x"], h, cfg.norm_eps)
                h = h + attn_mod.cross_decode_attention(
                    lp["cross"], hx, cfg, cross_k=xk, cross_v=xv,
                    compute_dtype=dt)
                from .layers import mlp
                h = h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h, cfg.norm_eps), dt)
                return h, (ck, cv)

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["stack"]["layers"], state["k"], state["v"],
                          state["cross_k"], state["cross_v"]))
            new_state = {"k": ks, "v": vs,
                         "cross_k": state["cross_k"],
                         "cross_v": state["cross_v"]}
        else:
            raise ValueError(fam)

        h = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = unembed(self.head(params), h, dt)
        return logits, new_state

    # ------------------------------------------------- decode state specs

    def decode_state_specs(self, rules):
        """PartitionSpec pytree matching init_decode_state's structure:
        batch over DP axes, heads/d_inner over the model axis."""
        from jax.sharding import PartitionSpec as P
        cfg = self.cfg
        b, h = rules.batch, rules.kv_heads
        hh = rules.heads
        ks = getattr(rules, "kv_seq", None)
        fam = cfg.family

        def kv(lead=1):
            return {"k": P(*(None,) * lead, b, ks, h, None),
                    "v": P(*(None,) * lead, b, ks, h, None)}

        if fam in ("dense", "moe", "vlm"):
            return kv()
        if fam == "ssm":
            if cfg.ssm_version == 1:
                return {"h": P(None, b, hh, None),
                        "conv": P(None, b, None, hh)}
            return {"h": P(None, b, hh, None, None),
                    "conv": P(None, b, None, hh)}
        if fam == "hybrid":
            if cfg.ssm_version == 1:
                m = {"h": P(None, None, b, hh, None),
                     "conv": P(None, None, b, None, hh)}
                t = {"h": P(None, b, hh, None),
                     "conv": P(None, b, None, hh)}
            else:
                m = {"h": P(None, None, b, hh, None, None),
                     "conv": P(None, None, b, None, hh)}
                t = {"h": P(None, b, hh, None, None),
                     "conv": P(None, b, None, hh)}
            out = {"mamba": m, "attn": kv(lead=1)}
            if cfg.n_layers % cfg.attn_every:
                out["tail"] = t
            return out
        if fam == "encdec":
            d = kv()
            d["cross_k"] = P(None, b, None, h, None)
            d["cross_v"] = P(None, b, None, h, None)
            return d
        raise ValueError(fam)

    # --------------------------------------------------------- input specs

    def input_specs(self, shape: ShapeConfig, *, abstract: bool = True):
        """Inputs for the step function of a shape cell (SDS stand-ins).

        train  -> {"tokens","labels"} (+"enc_emb"/"frontend_emb")
        prefill-> {"tokens"} (+frontend inputs)
        decode -> {"token","pos","state"}
        """
        cfg = self.cfg
        dt = _dt(cfg)
        B, S = shape.global_batch, shape.seq_len

        def mk(shp, dtype):
            if abstract:
                return jax.ShapeDtypeStruct(shp, dtype)
            if dtype == jnp.int32:
                return jnp.zeros(shp, dtype)
            return jnp.zeros(shp, dtype)

        def frontend_inputs(d):
            if cfg.family == "encdec":
                d["enc_emb"] = mk((B, cfg.enc_seq_len, cfg.d_model), dt)
            elif cfg.family == "vlm":
                nf = min(cfg.n_frontend_tokens, S // 2)
                d["frontend_emb"] = mk((B, nf, cfg.d_model), dt)
            return d

        if shape.kind == "train":
            return frontend_inputs({
                "tokens": mk((B, S), jnp.int32),
                "labels": mk((B, S), jnp.int32),
            })
        if shape.kind == "prefill":
            return frontend_inputs({"tokens": mk((B, S), jnp.int32)})
        if shape.kind == "decode":
            return {
                "token": mk((B, 1), jnp.int32),
                "pos": mk((), jnp.int32),
                "state": self.init_decode_state(B, S, abstract=abstract),
            }
        raise ValueError(shape.kind)
