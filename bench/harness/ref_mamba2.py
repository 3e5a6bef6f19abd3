"""Plain reference of a Mamba-2 language model's training step: the
weights from a seed, the forward pass, the loss and its gradient, and
AdamW, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  Nothing here imports the
program.

Written from Dao and Gu, "Transformers are SSMs" (arXiv:2405.21060), §7
and Listing 1.  A layer is

    h = RMSNorm(u)
    z, x, B, C, dt = h W_z, h W_x, h W_B, h W_C, h W_dt      (in_proj)
    x, B, C = silu(causal depthwise conv_4(x, B, C) + bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (one per head)
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T;  y_t = s_t C_t  (SSD)
    y = y + D x;  y = RMSNorm(y * silu(z)) * out_norm;  u += y W_out

followed by a final RMSNorm and the tied embedding as the output head.
The recurrence is computed by the paper's chunked SSD algorithm (Listing
1, its stable ``segsum``; the chunk states are passed on with the
chunk-level ``segsum`` instead of a sequential scan), which equals the
recurrence to rounding (``bench/tests`` checks it against the plain
recurrence).  Departures, each chosen to match the configuration as the
program registers it: the RMSNorm epsilon is ``norm_eps``; B and C are
shared by all heads (one group); the embedding table has the program's
padded rows (``vocab`` rounded up to 256), which no token selects and
the loss leaves out; the loss is the cross-entropy summed over a rank's
tokens and divided by the global token count, so that the ranks'
gradients add up to the gradient of the global mean.

The weights are the harness's, made here from the seed in the program's
parameter layout (a ``units`` stack of ``n_layers``), so the program and
the reference start from the same numbers and neither takes them from
the other.

``act`` rounds activations, and their cotangents, to a lower precision
with a per-tensor scale, where the program computes in its activation
dtype: the control (``float8_e4m3fn`` for a bfloat16 configuration).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def widths(m: dict) -> tuple:
    """(d_model, d_inner, heads, head_dim, d_state, d_conv)."""
    s = m["ssm"]
    d_in = s["expand"] * m["d_model"]
    return (m["d_model"], d_in, d_in // s["head_dim"], s["head_dim"],
            s["d_state"], s["d_conv"])


def key_of(seed: int):
    """The key of a run's weights: any whole seed, however large."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32 & 0xFFFFFFFF))


def init(m: dict, key) -> dict:
    """The weights of a run, from its key, in the program's layout.
    Projections are normal with the fan-in's inverse square root as
    scale, convolutions normal at 0.1 with zero bias, A uniform in
    [1, 16] and dt in [0.001, 0.1] log-uniform (dt_bias its inverse
    softplus), as the paper initialises them; norms, D and out_norm 1."""
    d, d_in, H, P, N, K = widths(m)
    L, V = m["n_layers"], padded_vocab(m)
    ks = iter(jax.random.split(key, 16))
    normal = lambda shape, std: jax.random.normal(next(ks), shape) * std
    dt = jnp.exp(jax.random.uniform(next(ks), (L, H),
                                    minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    mixer = {
        "in_z": normal((L, d, d_in), d ** -0.5),
        "in_x": normal((L, d, d_in), d ** -0.5),
        "in_B": normal((L, d, N), d ** -0.5),
        "in_C": normal((L, d, N), d ** -0.5),
        "in_dt": normal((L, d, H), d ** -0.5),
        "conv_x": normal((L, K, d_in), 0.1),
        "conv_xb": jnp.zeros((L, d_in)),
        "conv_B": normal((L, K, N), 0.1),
        "conv_Bb": jnp.zeros((L, N)),
        "conv_C": normal((L, K, N), 0.1),
        "conv_Cb": jnp.zeros((L, N)),
        "A_log": jnp.log(jax.random.uniform(next(ks), (L, H), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((L, H)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "out_norm": jnp.ones((L, d_in)),
        "out_proj": normal((L, d_in, d), d_in ** -0.5),
    }
    return {"embed": normal((V, d), d ** -0.5),
            "final_norm": {"scale": jnp.ones((d,))},
            "units": {"layer0": {"norm1": {"scale": jnp.ones((L, d))},
                                 "mixer": mixer}}}


# ---------------------------------------------------------------------------
# Lower precision for the control
# ---------------------------------------------------------------------------


def _round(x, dtype):
    """x rounded to ``dtype`` with a per-tensor scale that maps its
    largest magnitude to the dtype's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(x.dtype) * s


def rounder(dtype: Optional[str]):
    """Identity for the reference; for the control, rounding of the
    forward value and of its cotangent to ``dtype``."""
    if dtype is None:
        return lambda x: x
    dt = jnp.dtype(dtype)

    @jax.custom_vjp
    def q(x):
        return _round(x, dt)

    q.defvjp(lambda x: (_round(x, dt), None),
             lambda _, g: (_round(g, dt),))
    return q


# ---------------------------------------------------------------------------
# Forward pass and loss
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence: x (B, S, C), w
    (K, C); output t sees inputs t-K+1 .. t."""
    K, C = w.shape
    y = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C)
    return y + b


def segsum(x):
    """x (..., T) -> (..., T, T): entry (i, j) is x_{j+1} + ... + x_i for
    j <= i and -inf above the diagonal; summed segment by segment, not
    as a difference of running sums (the paper's stable form)."""
    T = x.shape[-1]
    xr = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    xr = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xr, 0.0)
    out = jnp.cumsum(xr, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(x, dA, B, C, chunk: int):
    """The SSD recurrence by chunks (Listing 1).  x (b, l, h, p) already
    times dt, dA (b, l, h) = dt A, B and C (b, l, n); zero initial
    state.  Returns y (b, l, h, p)."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    c = l // chunk
    x = x.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, n)
    C = C.reshape(b, c, chunk, n)
    A = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)     # (b, h, c, q)
    A_cum = jnp.cumsum(A, axis=-1)
    L = jnp.exp(segsum(A))                                    # (b,h,c,q,q)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", C, B, L, x)
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                  # (b, h, c, q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", B, decay, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                           (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cum))
    return (y_diag + y_off).reshape(b, l, h, p)


def mamba_layer(m: dict, p: dict, u, q):
    """One residual layer: u (B, S, d) -> u + mixer(RMSNorm(u))."""
    d, d_in, H, P, N, K = widths(m)
    Bsz, S, _ = u.shape
    eps = m["norm_eps"]
    h = q(rms_norm(u, p["norm1"]["scale"], eps))
    w = p["mixer"]
    mm = lambda a, name: q(a @ q(w[name]))
    z, x, Bm, Cm, dt = (mm(h, k) for k in ("in_z", "in_x", "in_B", "in_C",
                                            "in_dt"))
    x = jax.nn.silu(causal_conv(x, w["conv_x"], w["conv_xb"]))
    Bm = jax.nn.silu(causal_conv(Bm, w["conv_B"], w["conv_Bb"]))
    Cm = jax.nn.silu(causal_conv(Cm, w["conv_C"], w["conv_Cb"]))
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # (B, S, H)
    A = -jnp.exp(w["A_log"])
    xh = x.reshape(Bsz, S, H, P)
    y = ssd(xh * dt[..., None], dt * A, Bm, Cm, min(m["ssm"]["chunk"], S))
    y = y + xh * w["D"][:, None]
    y = y.reshape(Bsz, S, d_in) * jax.nn.silu(z)
    y = q(rms_norm(y, w["out_norm"], eps))
    return q(u + mm(y, "out_proj"))


def loss(m: dict, params: dict, tokens, labels, total_tokens: int,
         act: Optional[str] = None):
    """Cross-entropy of ``labels`` summed over the rows given, over
    ``total_tokens``."""
    q = rounder(act)
    V = m["vocab_size"]
    emb = params["embed"][:V]
    u = q(emb[tokens])
    layer = jax.checkpoint(functools.partial(mamba_layer, m, q=q))

    def body(u, p):
        return layer(p, u), None

    u, _ = jax.lax.scan(body, u, params["units"]["layer0"])
    u = q(rms_norm(u, params["final_norm"]["scale"], m["norm_eps"]))
    logits = q(u @ q(emb).T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked) / total_tokens


# ---------------------------------------------------------------------------
# The training step: per-rank gradients, their sum, AdamW
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """The learning rate of step ``step`` (0 first): linear warm-up, then
    a cosine down to ``min_lr_frac``."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def adamw(opt: dict, step, params, grads, m, v):
    """AdamW with the gradient clipped to a global norm of
    ``grad_clip``; ``step`` (0 first) as ``(lr, 1 - b1^t, 1 - b2^t)``.
    Returns (params, m, v, clipped gradient)."""
    b1, b2 = opt["betas"]
    lr, c1, c2 = step
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    s = jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-9))
    g = jax.tree.map(lambda x: x * s, grads)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, g


def rank_grads(m: dict, mesh, axis: str, total_tokens: int, rows: int,
               act: Optional[str] = None, summed: bool = True):
    """``fn(params, tokens, labels) -> (grads, loss)`` over ``mesh``:
    every rank takes its share of the batch, ``rows`` sequences at a
    time, and adds up its gradient.  ``summed``: the ranks' gradients
    summed, as every rank holds them; else each rank's own, stacked over
    ``axis``.  The loss is the ranks' sum either way."""
    from jax.sharding import PartitionSpec as Ps

    def rank(params, tokens, labels):
        n = tokens.shape[0] // rows
        tk = tokens.reshape(n, rows, -1)
        lb = labels.reshape(n, rows, -1)
        g0 = jax.tree.map(jnp.zeros_like, params)

        def one(carry, xs):
            acc, total = carry
            val, g = jax.value_and_grad(loss, argnums=1)(
                m, params, xs[0], xs[1], total_tokens, act)
            return (jax.tree.map(jnp.add, acc, g), total + val), None

        (g, val), _ = jax.lax.scan(one, (g0, jnp.zeros(())), (tk, lb))
        g = jax.tree.map((lambda x: jax.lax.psum(x, axis)) if summed
                         else (lambda x: x[None]), g)
        return g, jax.lax.psum(val, axis)

    return jax.jit(jax.shard_map(
        rank, mesh=mesh, in_specs=(Ps(), Ps(axis), Ps(axis)),
        out_specs=(Ps() if summed else Ps(axis), Ps()), check_vma=False))


def make_step(m: dict, opt: dict, mesh, axis: str, total_tokens: int,
              rows: int, act: Optional[str] = None):
    """The reference step over ``mesh``: the ranks' gradients
    (:func:`rank_grads`) summed, and AdamW applied alike on every rank.
    ``step_fn(step, params, m, v, tokens, labels)`` returns (params, m,
    v, loss, clipped gradient)."""
    summed = rank_grads(m, mesh, axis, total_tokens, rows, act)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def update(step, params, mo, vo, grads):
        return adamw(opt, step, params, grads, mo, vo)

    b1, b2 = opt["betas"]

    def step_fn(step, params, mo, vo, tokens, labels):
        at = np.array([lr_at(opt, step), 1 - b1 ** (step + 1),
                       1 - b2 ** (step + 1)], np.float32)
        with jax.default_matmul_precision("highest"):
            grads, val = summed(params, tokens, labels)
            params, mo, vo, g = update(tuple(at), params, mo, vo, grads)
        return params, mo, vo, val, g

    return step_fn
