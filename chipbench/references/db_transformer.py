"""Plain reference of a DiffusionBlocks transformer (arXiv:2506.14202),
written from the paper and the configuration alone: straightforward
``jax.numpy`` in float32 at ``Precision.HIGHEST``, no kernels, no cache, no
batching tricks. It imports nothing of the program under test and takes
nothing the program made: weights come from ``harness.weights`` by path.

What it follows (paper §3, App. C/E.4; EDM, Karras et al. 2022):
- B blocks of L/B layers; block b learns the noise range between the
  equi-probability edges σ_b, σ_{b+1} of p_noise = logN(P_mean, P_std²)
  truncated to [σ_min, σ_max], widened by the overlap γ.
- Training (AR adapter, concat mode): the stream is [clean ‖ c_in·z] with
  z = emb(x) + σε; clean i sees clean j ≤ i, noisy i sees clean j < i and
  itself; rope phases of the noisy copy are those of its clean token. Each
  layer is pre-norm attention + SwiGLU with DiT-style AdaLN (shift, scale,
  gate from the σ embedding) applied to the noisy half only. D = c_skip·z +
  c_out·F; the loss is the CE of the readout of D against x.
- Serving: the clean context of every layer is its block's layers run
  causally from raw embeddings; the next token's embedding is denoised from
  σ_max through every block (one Euler step per block), and the readout of
  the result gives the logits.
- AdamW (decoupled decay, global-norm clipping, linear warmup + cosine).

``quant="fp8"`` computes every matrix product with both operands rounded
to float8_e4m3fn under a per-tensor absmax scale: the control, one
precision step below the bfloat16 the configurations state."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtr, ndtri

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# configuration -> layout, noise ranges
# ---------------------------------------------------------------------------

class Spec:
    def __init__(self, cfg: dict):
        m, db = cfg["model"], cfg["diffusion_blocks"]
        self.L, self.d = m["n_layers"], m["d_model"]
        self.H, self.KV = m["n_heads"], m["n_kv_heads"]
        self.hd = m.get("head_dim") or self.d // self.H
        self.ff, self.V = m["d_ff"], m["vocab_size"]
        self.norm, self.theta = m["norm"], m.get("rope_theta", 10000.0)
        self.tied = m.get("tie_embeddings", False)
        assert m["mlp"] == "swiglu" and m["family"] == "dense"
        self.nb = db["num_blocks"]
        self.gamma = db["overlap_gamma"]
        self.p_mean, self.p_std = db["p_mean"], db["p_std"]
        self.s_min, self.s_max = db["sigma_min"], db["sigma_max"]
        self.s_data = db["sigma_data"]
        self.cond_dim = db["cond_dim"]
        self.l2_embed = db["embed_l2_normalize"]
        assert db["causal_mode"] == "concat" and db["loss"] == "ce"
        assert db["partition"] == "equiprob"
        per = self.L // self.nb
        assert per * self.nb == self.L
        self.ranges = [(b * per, per) for b in range(self.nb)]

    def layout(self) -> Dict[str, tuple]:
        d, L, hd = self.d, self.L, self.hd
        lay = {"cond/mlp1/w": (self.cond_dim, d), "cond/mlp2/w": (d, d),
               "embed/table": (self.V, d)}
        if self.norm != "nonparam_ln":
            lay["final_norm/g"] = (d,)
            lay["layers/ln1/g"] = (L, d)
            lay["layers/ln2/g"] = (L, d)
        if not self.tied:
            lay["head/w"] = (d, self.V)
        lay.update({
            "layers/adaln/b": (L, 6 * d), "layers/adaln/w": (L, d, 6 * d),
            "layers/attn/wq": (L, d, self.H * hd),
            "layers/attn/wk": (L, d, self.KV * hd),
            "layers/attn/wv": (L, d, self.KV * hd),
            "layers/attn/wo": (L, self.H * hd, d),
            "layers/mlp/wi": (L, d, self.ff), "layers/mlp/wg": (L, d, self.ff),
            "layers/mlp/wo": (L, self.ff, d)})
        return lay

    # noise ranges (host, float64)
    def _q(self, s):
        return ndtr((np.log(s) - self.p_mean) / self.p_std)

    def _s(self, q):
        return np.exp(self.p_mean + self.p_std * ndtri(q))

    def edges(self) -> np.ndarray:
        """Descending σ edges, σ_max ... σ_min."""
        q0, q1 = self._q(self.s_min), self._q(self.s_max)
        asc = self._s(q0 + np.arange(self.nb + 1) / self.nb * (q1 - q0))
        asc[0], asc[-1] = self.s_min, self.s_max
        return asc[::-1].copy()

    def qrange(self, b: int) -> Tuple[float, float]:
        e = self.edges()
        hi, lo = float(e[b]), float(e[b + 1])
        if self.gamma > 0:
            a = (hi / lo) ** self.gamma
            lo, hi = max(lo / a, self.s_min), min(hi * a, self.s_max)
        return float(self._q(lo)), float(self._q(hi))

    def schedule(self) -> List[Tuple[int, float, float]]:
        """One Euler step per block: (block, σ_from, σ_to); ends at 0."""
        e = self.edges()
        return [(b, float(e[b]), 0.0 if b == self.nb - 1 else float(e[b + 1]))
                for b in range(self.nb)]


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _q8(x):
    """float8_e4m3fn under a per-tensor absmax scale in the forward pass;
    straight through in the backward pass (the products' gradients then
    see the rounded operands, as fp8 training computes them)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(F8).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


class Ops:
    def __init__(self, quant: Optional[str] = None):
        assert quant in (None, "fp8")
        self.quant = quant

    def ein(self, spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.quant:
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, g, kind):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


def _rope(x, pos, theta):
    """x (..., S, H, hd), pos (S,) or (..., S)."""
    half = x.shape[-1] // 2
    fr = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., :, None, None] * fr
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def precond(sigma, sd):
    s2, d2 = sigma ** 2, sd ** 2
    return d2 / (s2 + d2), sigma * sd / jnp.sqrt(s2 + d2), 1 / jnp.sqrt(s2 + d2)


class Model:
    """Forward arithmetic over one parameter tree (fp32 leaves)."""

    def __init__(self, spec: Spec, quant: Optional[str] = None):
        self.sp, self.o = spec, Ops(quant)

    def table(self, p):
        t = p["embed"]["table"]
        if self.sp.l2_embed:
            n = jnp.sqrt(jnp.sum(t * t, -1, keepdims=True))
            t = t / jnp.maximum(n, 1e-6)
        return t

    def cond(self, p, sigma):
        """σ (N,) -> AdaLN conditioning (N, d)."""
        half = self.sp.cond_dim // 2
        fr = jnp.exp(jnp.linspace(0.0, 6.0, half))
        ang = (jnp.log(sigma) / 4.0)[:, None] * fr
        ff = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)
        h = jax.nn.silu(self.o.ein("nc,cd->nd", ff, p["cond"]["mlp1"]["w"]))
        return jax.nn.silu(self.o.ein("nc,cd->nd", h, p["cond"]["mlp2"]["w"]))

    def mods(self, lp, cond):
        m = self.o.ein("nd,de->ne", cond, lp["adaln"]["w"]) + lp["adaln"]["b"]
        d = self.sp.d
        return [m[:, None, i * d:(i + 1) * d] for i in range(6)]

    def logits(self, p, h):
        g = p.get("final_norm", {}).get("g")
        x = _norm(h, g, self.sp.norm)
        w = self.table(p).T if self.sp.tied else p["head"]["w"]
        return self.o.ein("...d,dv->...v", x, w)

    def qkv(self, lp, x, pos):
        sp, a = self.sp, lp["attn"]
        q = self.o.ein("...sd,de->...se", x, a["wq"])
        k = self.o.ein("...sd,de->...se", x, a["wk"])
        v = self.o.ein("...sd,de->...se", x, a["wv"])
        sh = x.shape[:-1]
        q = _rope(q.reshape(*sh, sp.H, sp.hd), pos, sp.theta)
        k = _rope(k.reshape(*sh, sp.KV, sp.hd), pos, sp.theta)
        return q, k, v.reshape(*sh, sp.KV, sp.hd)

    def attend(self, q, k, v, mask):
        """q (N,Sq,H,hd), k/v (N,Sk,KV,hd), mask (Sq,Sk) or (N,Sq,Sk)."""
        sp = self.sp
        G = sp.H // sp.KV
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        s = self.o.ein("nqhd,nkhd->nhqk", q, k) / math.sqrt(sp.hd)
        m = mask[:, None] if mask.ndim == 3 else mask[None, None]
        s = jnp.where(m, s, -1e30)
        w = jax.nn.softmax(s, -1)
        return self.o.ein("nhqk,nkhd->nqhd", w, v)

    def mlp(self, lp, x):
        m = lp["mlp"]
        h = jax.nn.silu(self.o.ein("...d,df->...f", x, m["wg"])) * \
            self.o.ein("...d,df->...f", x, m["wi"])
        return self.o.ein("...f,fd->...d", h, m["wo"])

    def proj_out(self, lp, o):
        return self.o.ein("...e,ed->...d", o.reshape(*o.shape[:-2], -1),
                          lp["attn"]["wo"])

    # ---- training: one layer over the [clean || noisy] stream -------------
    def train_layer(self, lp, h, cond, S):
        sp = self.sp
        s1, c1, g1, s2, c2, g2 = self.mods(lp, cond)
        noisy = (jnp.arange(2 * S) >= S)[None, :, None]
        g_ln1 = lp.get("ln1", {}).get("g")
        g_ln2 = lp.get("ln2", {}).get("g")
        pos = jnp.concatenate([jnp.arange(S), jnp.arange(S)])
        x = _norm(h, g_ln1, sp.norm)
        x = jnp.where(noisy, x * (1 + c1) + s1, x)
        q, k, v = self.qkv(lp, x, pos)
        i = jnp.arange(2 * S)
        qi, ki = i[:, None], i[None, :]
        mask = (((qi < S) & (ki < S) & (ki <= qi))
                | ((qi >= S) & (ki < S) & (ki < qi - S))
                | ((qi >= S) & (ki == qi)))
        a = self.proj_out(lp, self.attend(q, k, v, mask))
        h = h + jnp.where(noisy, a * (1 + g1), a)
        x = _norm(h, g_ln2, sp.norm)
        x = jnp.where(noisy, x * (1 + c2) + s2, x)
        m = self.mlp(lp, x)
        return h + jnp.where(noisy, m * (1 + g2), m)

    def block_loss_sum(self, view, tokens, sigma, eps, n_layers):
        """Sum over rows and positions of the CE of block ``view`` (its
        layers stacked along axis 0) on ``tokens`` (N, S)."""
        sp = self.sp
        S = tokens.shape[1]
        emb = self.table(view)[tokens]
        z = emb + sigma * eps
        c_skip, c_out, c_in = precond(sigma, sp.s_data)
        h = jnp.concatenate([emb, c_in * z], 1)
        cond = self.cond(view, sigma.reshape(-1))

        def body(h, lp):
            return jax.checkpoint(self.train_layer,
                                  static_argnums=(3,))(lp, h, cond, S), None
        h, _ = jax.lax.scan(body, h, view["layers"], length=n_layers)
        dh = c_skip * z + c_out * h[:, S:]
        lg = self.logits(view, dh)
        lse = jax.nn.logsumexp(lg, -1)
        tgt = jnp.take_along_axis(lg, tokens[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt)


# ---------------------------------------------------------------------------
# training reference: the first steps of block-wise training
# ---------------------------------------------------------------------------

def block_view(params, start, size):
    v = {k: val for k, val in params.items() if k != "layers"}
    v["layers"] = jax.tree_util.tree_map(lambda x: x[start:start + size],
                                         params["layers"])
    return v


def write_view(params, view, start):
    out = dict(params)
    out.update({k: val for k, val in view.items() if k != "layers"})
    out["layers"] = jax.tree_util.tree_map(
        lambda whole, blk: whole.at[start:start + blk.shape[0]].set(blk),
        params["layers"], view["layers"])
    return out


def noise(spec: Spec, b: int, rng, shape):
    """σ (N,1,1) and ε (N,S,d) of one block step, drawn from ``rng``."""
    r_sig, r_eps = jax.random.split(rng)
    q_lo, q_hi = spec.qrange(b)
    u = jax.random.uniform(r_sig, (shape[0], 1, 1), minval=q_lo,
                           maxval=q_hi)
    from jax.scipy.special import ndtri as jndtri
    sigma = jnp.exp(spec.p_mean + spec.p_std * jndtri(u))
    eps = jax.random.normal(r_eps, shape + (spec.d,), jnp.float32)
    return sigma, eps


def lr_at(step, opt: dict):
    """Linear warmup then cosine to 10% over ``opt['schedule_steps']``."""
    base, warm, total = opt["lr"], opt["warmup_steps"], opt["schedule_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


class Trainer:
    """Block-wise training in the plain reference: grads of one block's
    view (its layers + the shared periphery) in row blocks of ``rows``,
    then AdamW on that view with the block's own optimizer state."""

    def __init__(self, spec: Spec, opt: dict, quant=None, rows: int = 4):
        self.sp, self.opt, self.rows = spec, opt, rows
        self.m = Model(spec, quant)
        self._grad = {}

    def grad_fn(self, b):
        if b in self._grad:
            return self._grad[b]
        size = self.sp.ranges[b][1]
        m, rows = self.m, self.rows

        def loss_and_grad(view, tokens, sigma, eps):
            N, S = tokens.shape
            nmb = N // rows
            tk = tokens.reshape(nmb, rows, S)
            sg = sigma.reshape(nmb, rows, 1, 1)
            ep = eps.reshape(nmb, rows, S, -1)
            vg = jax.value_and_grad(
                lambda v, t, s, e: m.block_loss_sum(v, t, s, e, size))

            def body(acc, xs):
                l, g = vg(view, *xs)
                return jax.tree_util.tree_map(jnp.add, acc,
                                              (l, g)), None
            zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like,
                                                          view))
            (l, g), _ = jax.lax.scan(body, zero, (tk, sg, ep))
            scale = 1.0 / (N * S)
            return l * scale, jax.tree_util.tree_map(lambda x: x * scale, g)
        self._grad[b] = jax.jit(loss_and_grad)
        return self._grad[b]

    def _adamw(self, tree, g, st):
        """AdamW (no clipping) on ``tree`` with state ``st`` (or None)."""
        o = self.opt
        if st is None:
            z = jax.tree_util.tree_map(jnp.zeros_like, tree)
            st = (0, z, z)
        t, mu, nu = st
        t += 1
        lr = lr_at(t, o)
        b1, b2 = o["b1"], o["b2"]
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        tree = jax.tree_util.tree_map(
            lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                      + o["eps"])
                                        + o["weight_decay"] * p),
            tree, mu, nu)
        return tree, (t, mu, nu)

    def _clipped(self, b, view, tokens, rng):
        sigma, eps = noise(self.sp, b, rng, tokens.shape)
        loss, g = self.grad_fn(b)(view, jnp.asarray(tokens), sigma, eps)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in
                          jax.tree_util.tree_leaves(g)))
        c = jnp.minimum(1.0, self.opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        return float(loss), jax.tree_util.tree_map(lambda x: x * c, g)

    def parallel_step(self, params, states, tokens, rngs):
        """Every block on the same batch (block-parallel training): each
        block's view grads clipped by their own global norm; each block's
        layers updated with its own AdamW state; the periphery updated
        once with the mean of the blocks' periphery grads. Returns (params,
        states, losses (B,), grads: the layers' per block and the periphery
        mean, in one tree)."""
        nb = self.sp.nb
        losses, per, layers = [], [], []
        for b in range(nb):
            start, size = self.sp.ranges[b]
            loss, g = self._clipped(b, block_view(params, start, size),
                                    tokens, rngs[b])
            losses.append(loss)
            layers.append(g["layers"])
            per.append({k: v for k, v in g.items() if k != "layers"})
        states = dict(states)
        new_layers = []
        for b in range(nb):
            start, size = self.sp.ranges[b]
            lv = jax.tree_util.tree_map(lambda x: x[start:start + size],
                                        params["layers"])
            lv, states[b] = self._adamw(lv, layers[b], states.get(b))
            new_layers.append(lv)
        g_per = jax.tree_util.tree_map(lambda *xs: sum(xs) / nb, *per)
        periph = {k: v for k, v in params.items() if k != "layers"}
        periph, states["periphery"] = self._adamw(periph, g_per,
                                                  states.get("periphery"))
        cat = lambda *xs: jnp.concatenate(xs, 0)  # noqa: E731
        params = dict(periph, layers=jax.tree_util.tree_map(cat, *new_layers))
        grads = dict(g_per, layers=jax.tree_util.tree_map(cat, *layers))
        return params, states, losses, grads

    def step(self, params, states, b, tokens, rng):
        """One block step. Returns (params, states, loss, clipped grads)."""
        start, size = self.sp.ranges[b]
        view = block_view(params, start, size)
        sigma, eps = noise(self.sp, b, rng, tokens.shape)
        loss, g = self.grad_fn(b)(view, jnp.asarray(tokens), sigma, eps)
        o = self.opt
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in
                          jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, o["grad_clip"] /
                                      jnp.maximum(gn, 1e-9)), g)
        st = states.get(b)
        if st is None:
            z = jax.tree_util.tree_map(jnp.zeros_like, view)
            st = (0, z, z)
        t, mu, nu = st
        t += 1
        lr = lr_at(t, o)
        b1, b2 = o["b1"], o["b2"]
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        view = jax.tree_util.tree_map(
            lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                      + o["eps"])
                                        + o["weight_decay"] * p),
            view, mu, nu)
        states = dict(states)
        states[b] = (t, mu, nu)
        return write_view(params, view, start), states, float(loss), g


# ---------------------------------------------------------------------------
# serving reference: the logits every served token was drawn from
# ---------------------------------------------------------------------------

class Server:
    def __init__(self, spec: Spec, quant=None):
        self.sp, self.m = spec, Model(spec, quant)
        self._fn = {}

    def _build(self, n_ctx, T):
        sp, m = self.sp, self.m
        sched = sp.schedule()

        def fn(p, ctx_tokens, plen, z0):
            """ctx_tokens (n_ctx,) = prompt ++ served[:-1] (padded);
            z0 (T, d): each served token's initial noise; served token t
            sits at position plen + t. Returns logits (T, V)."""
            p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
            table = m.table(p)
            emb = table[ctx_tokens][None]                    # (1, n, d)
            pos_c = jnp.arange(n_ctx)
            causal = pos_c[None, :] <= pos_c[:, None]
            pos_n = plen + jnp.arange(T)                     # (T,)
            see = pos_c[None, :] < pos_n[:, None]            # (T, n)
            kv = []                                          # per layer
            for b in range(sp.nb):
                start, size = sp.ranges[b]
                h = emb
                for li in range(start, start + size):
                    lp = jax.tree_util.tree_map(lambda x: x[li],
                                                p["layers"])
                    g1 = lp.get("ln1", {}).get("g")
                    g2 = lp.get("ln2", {}).get("g")
                    x = _norm(h, g1, sp.norm)
                    q, k, v = m.qkv(lp, x, pos_c)
                    kv.append((k[0], v[0]))
                    h = h + m.proj_out(lp, m.attend(q, k, v, causal))
                    h = h + m.mlp(lp, _norm(h, g2, sp.norm))
            z = z0
            for b, s_from, s_to in sched:
                start, size = sp.ranges[b]
                sig = jnp.full((T,), s_from, jnp.float32)
                c_skip, c_out, c_in = precond(sig[:, None], sp.s_data)
                cond = m.cond(p, sig)
                h = (c_in * z)[:, None]                      # (T, 1, d)
                for li in range(start, start + size):
                    lp = jax.tree_util.tree_map(lambda x: x[li],
                                                p["layers"])
                    s1, c1, g1_, s2, c2, g2_ = m.mods(lp, cond)
                    x = _norm(h, lp.get("ln1", {}).get("g"), sp.norm)
                    x = x * (1 + c1) + s1
                    q, k, v = m.qkv(lp, x, pos_n[:, None])   # (T,1,H,hd)
                    kc, vc = kv[li]                          # (n, KV, hd)
                    G = sp.H // sp.KV
                    kc_ = jnp.repeat(kc, G, 1)
                    vc_ = jnp.repeat(vc, G, 1)
                    k_ = jnp.repeat(k[:, 0], G, 1)           # (T, H, hd)
                    v_ = jnp.repeat(v[:, 0], G, 1)
                    sc = m.o.ein("thd,nhd->thn", q[:, 0], kc_)
                    sc = jnp.where(see[:, None, :], sc / math.sqrt(sp.hd),
                                   -1e30)
                    ss = jnp.sum(q[:, 0] * k_, -1, keepdims=True) / \
                        math.sqrt(sp.hd)
                    w = jax.nn.softmax(jnp.concatenate([sc, ss], -1), -1)
                    o = m.o.ein("thn,nhd->thd", w[..., :-1], vc_) + \
                        w[..., -1:] * v_
                    a = m.proj_out(lp, o[:, None])
                    h = h + a * (1 + g1_)
                    x = _norm(h, lp.get("ln2", {}).get("g"), sp.norm)
                    x = x * (1 + c2) + s2
                    h = h + m.mlp(lp, x) * (1 + g2_)
                d_hat = c_skip * z + c_out * h[:, 0]
                if s_to > 0:
                    r = s_to / s_from
                    z = r * z + (1 - r) * d_hat
                else:
                    z = d_hat
            return m.logits(p, z)
        return jax.jit(fn)

    def logits(self, params, prompt, served, z0, ctx_len: int, out_len: int):
        """Reference logits (T, V) for the T served tokens of one request,
        padded to one shape (``ctx_len`` context, ``out_len`` tokens) so
        that every request runs the same compiled program."""
        plen, T = len(prompt), len(served)
        ctx = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n, Tp = ctx_len, out_len
        key = (n, Tp)
        if key not in self._fn:
            self._fn[key] = self._build(n, Tp)
        ctx = np.pad(ctx, (0, n - len(ctx)))
        zp = np.zeros((Tp, self.sp.d), np.float32)
        zp[:T] = z0
        out = self._fn[key](params, jnp.asarray(ctx), jnp.int32(plen),
                            jnp.asarray(zp))
        return np.asarray(out[:T])
