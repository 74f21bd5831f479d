"""Plain float32 reference of a pre-norm decoder-only transformer, the
weights the benchmark serves, and the fp8 control.

Nothing here imports the program.  A model file (bench/models/<type>.py)
turns its published config into the `Arch` below; this module does the
rest:

  * `init_params` draws the weights from a seed in one jitted call, on
    the devices of the cell, in the dtype they are served in, laid out
    as the tree `LLM.load(params=...)` takes;
  * `token_logits` runs the reference over prompts and the tokens a run
    served, layer by layer, in float32 at HIGHEST matmul precision, and
    reads for every served token how far its logit lies below the
    reference's best;
  * with `mode="fp8"` the same forward rounds both operands of every
    matmul to float8_e4m3 (weights per output column, activations per
    row): a precision below the bfloat16 the configurations state, and
    the control that `correct` has to reject.

Tensor parallelism changes the math only in SPD-dropped blocks (the
paper's Fig. 3): shard i computes its heads' attention partial P_i,
feeds x + P_i (+ b_o) through the layer norm and ITS slice of the MLP,
and one all-reduce sums z_i + P_i.  The reference computes the same
per-shard sum.  Shard i holds kv heads [i*Hkv/tp, (i+1)*Hkv/tp) with
their query heads and MLP columns [i*ff/tp, (i+1)*ff/tp)."""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 512          # reference sequences pad to a multiple of this
Q_CHUNK = 512             # query rows per attention block


@dataclass(frozen=True)
class Arch:
    """What the reference needs to know of a model, from its config."""

    d: int                 # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    norm: str              # "rms" | "layer"
    eps: float
    act: str               # "silu" | "relu"
    gated: bool            # SwiGLU-style MLP
    bias: bool             # biases on every projection (OPT)
    qk_norm: bool          # per-head RMSNorm of q and k (Qwen3)
    rope_theta: float      # 0 = no rotary embedding
    learned_pos: int       # rows of a learned position table, 0 = none
    tied: bool             # output head is the embedding


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


def _fold(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))


def _layer_shapes(a: Arch) -> dict:
    """{group: {leaf: (shape, kind)}}; kind picks the draw: "w" a matrix
    scaled 1/sqrt(fan-in), "out" an output projection scaled down by
    sqrt(2 * layers) more, "one" a norm gain near 1, "b" a bias."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    norm = {"w": ((a.d,), "one")}
    if a.norm == "layer":
        norm["b"] = ((a.d,), "b")
    attn = {"wq": ((a.d, q), "w"), "wk": ((a.d, kv), "w"),
            "wv": ((a.d, kv), "w"), "wo": ((q, a.d), "out")}
    if a.bias:
        attn.update({"bq": ((q,), "b"), "bk": ((kv,), "b"),
                     "bv": ((kv,), "b"), "bo": ((a.d,), "b")})
    if a.qk_norm:
        attn.update({"qn": ((a.head_dim,), "one"),
                     "kn": ((a.head_dim,), "one")})
    mlp = {"wu": ((a.d, a.ff), "w"), "wd": ((a.ff, a.d), "out")}
    if a.gated:
        mlp["wg"] = ((a.d, a.ff), "w")
    if a.bias:
        mlp.update({"bu": ((a.ff,), "b"), "bd": ((a.d,), "b")})
    return {"ln1": norm, "attn": attn, "ln2": dict(norm), "mlp": mlp}


def _draw(key, shape, kind, a: Arch, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        z = z / np.sqrt(shape[0])
    elif kind == "out":
        z = z / np.sqrt(shape[0]) / np.sqrt(2 * a.layers)
    elif kind == "one":
        z = 1.0 + 0.1 * z
    else:
        z = 0.02 * z
    return z.astype(dtype)


def param_shapes(a: Arch) -> dict:
    """The canonical tree's leaf shapes (tuples), in its layout."""
    norm = {"w": (a.d,)}
    if a.norm == "layer":
        norm["b"] = (a.d,)
    tree = {"emb": (a.vocab, a.d), "lnf": norm,
            "layers": [jax.tree.map(lambda s: s[0], _layer_shapes(a),
                                    is_leaf=lambda x: isinstance(x, tuple)
                                    and isinstance(x[1], str))
                       for _ in range(a.layers)]}
    if a.learned_pos:
        tree["pos"] = (a.learned_pos, a.d)
    if not a.tied:
        tree["head"] = (a.d, a.vocab)
    return tree


def _init(key, a: Arch, dtype):
    def layer(i):
        k = jax.random.fold_in(key, 1000 + i)
        return {g: {n: _draw(_fold(k, f"{g}.{n}"), s, kind, a, dtype)
                    for n, (s, kind) in leaves.items()}
                for g, leaves in _layer_shapes(a).items()}

    p = {"emb": (0.02 * jax.random.normal(_fold(key, "emb"),
                                          (a.vocab, a.d))).astype(dtype),
         "lnf": {"w": _draw(_fold(key, "lnf.w"), (a.d,), "one", a, dtype)},
         "layers": [layer(i) for i in range(a.layers)]}
    if a.norm == "layer":
        p["lnf"]["b"] = _draw(_fold(key, "lnf.b"), (a.d,), "b", a, dtype)
    if a.learned_pos:
        p["pos"] = (0.02 * jax.random.normal(
            _fold(key, "pos"), (a.learned_pos, a.d))).astype(dtype)
    if not a.tied:
        p["head"] = _draw(_fold(key, "head"), (a.d, a.vocab), "w", a, dtype)
    return p


def init_params(seed: int, a: Arch, devices, dtype=jnp.bfloat16):
    """The weights of `seed`, made on `devices` in one jitted call (each
    leaf split over the devices along its largest axis that divides),
    then moved whole to the host CPU device, where `LLM.load` builds
    its placements."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def sharding(shape):
        for ax in np.argsort(shape)[::-1]:
            if shape[ax] % n == 0:
                spec = [None] * len(shape)
                spec[ax] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    shapes = param_shapes(a)
    shard = jax.tree.map(sharding, shapes,
                         is_leaf=lambda x: isinstance(x, tuple))
    made = jax.jit(partial(_init, a=a, dtype=dtype),
                   out_shardings=shard)(_key(seed))
    host = jax.local_devices(backend="cpu")[0]
    return jax.block_until_ready(jax.device_put(made, host))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _f8(x, axis):
    """float8_e4m3 with absmax scales along `axis` reduced, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode: str):
    """x (..., k) @ w (k, n): float32 at HIGHEST; "fp8": both operands
    rounded to float8_e4m3 (rows of x, columns of w scaled apart), the
    products summed in float32."""
    if mode == "fp8":
        return jnp.matmul(_f8(x, -1), _f8(w, 0), precision=HI)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, p, a: Arch):
    if a.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + a.eps) * p["w"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + a.eps) * p["w"] + p["b"]


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, H, dh), pos (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(x, a: Arch):
    return jax.nn.silu(x) if a.act == "silu" else jax.nn.relu(x)


def _attention(q, k, v, a: Arch):
    """Causal softmax attention; q (S, H, dh), k/v (S, Hkv, dh) -> (S, H,
    dh), in blocks of Q_CHUNK query rows."""
    s = q.shape[0]
    g = a.heads // a.kv_heads
    qg = q.reshape(s, a.kv_heads, g, a.head_dim) / np.sqrt(a.head_dim)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * Q_CHUNK, Q_CHUNK)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI)
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, -1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(s // Q_CHUNK))
    return o.reshape(s, a.heads, a.head_dim)


def _mlp(h, p, a: Arch, mode, cols=slice(None)):
    up = _mm(h, p["wu"][:, cols], mode)
    if a.bias:
        up = up + p["bu"][cols]
    hid = _act(_mm(h, p["wg"][:, cols], mode), a) * up if a.gated \
        else _act(up, a)
    return _mm(hid, p["wd"][cols], mode)


@partial(jax.jit, static_argnames=("a", "drop", "tp", "mode"))
def _layer(p, x, a: Arch, drop: bool, tp: int, mode: str):
    p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
    s = x.shape[0]
    pa = p["attn"]
    h = _norm(x, p["ln1"], a)
    q, k, v = (_mm(h, pa[n], mode) for n in ("wq", "wk", "wv"))
    if a.bias:
        q, k, v = q + pa["bq"], k + pa["bk"], v + pa["bv"]
    q = q.reshape(s, a.heads, a.head_dim)
    k = k.reshape(s, a.kv_heads, a.head_dim)
    v = v.reshape(s, a.kv_heads, a.head_dim)
    if a.qk_norm:
        rms = lambda t, w: t * jax.lax.rsqrt(
            jnp.mean(t * t, -1, keepdims=True) + a.eps) * w
        q, k = rms(q, pa["qn"]), rms(k, pa["kn"])
    if a.rope_theta:
        pos = jnp.arange(s)
        q, k = _rope(q, pos, a.rope_theta), _rope(k, pos, a.rope_theta)
    o = _attention(q, k, v, a).reshape(s, -1)
    bo = pa["bo"] if a.bias else 0.0
    bd = p["mlp"]["bd"] if a.bias else 0.0
    if not drop:
        u = x + _mm(o, pa["wo"], mode) + bo
        return u + _mlp(_norm(u, p["ln2"], a), p["mlp"], a, mode) + bd
    # SPD-dropped block: each shard's partial skips the attention sync
    qs = a.heads // tp * a.head_dim
    fs = a.ff // tp
    total = x + bo + bd
    for i in range(tp):
        part = _mm(o[:, i * qs:(i + 1) * qs], pa["wo"][i * qs:(i + 1) * qs],
                   mode)
        u_i = x + part + bo
        z_i = _mlp(_norm(u_i, p["ln2"], a), p["mlp"], a, mode,
                   slice(i * fs, (i + 1) * fs))
        total = total + z_i + part
    return total


@partial(jax.jit, static_argnames=("a",))
def _embed(emb, pos_table, toks, a: Arch):
    x = jnp.take(emb, toks, axis=0).astype(jnp.float32)
    if a.learned_pos:
        x = x + jnp.take(pos_table, jnp.arange(toks.shape[0]), axis=0,
                         mode="clip").astype(jnp.float32)
    return x


@partial(jax.jit, static_argnames=("a", "mode"))
def _readout(lnf, head, x, rows, served, other, a: Arch, mode: str):
    """Logits at `rows`; returns (best logit, logit of `served`, logit of
    `other`, argmax) per row."""
    lnf = jax.tree.map(lambda t: t.astype(jnp.float32), lnf)
    h = _norm(jnp.take(x, rows, axis=0), lnf, a)
    lg = _mm(h, head.astype(jnp.float32), mode)
    pick = lambda t: jnp.take_along_axis(lg, t[:, None], 1)[:, 0]
    return (jnp.max(lg, -1), pick(served), pick(other),
            jnp.argmax(lg, -1).astype(jnp.int32))


@dataclass
class Reading:
    """Per served token: the reference's best logit, the logit of the
    token served, and of the token a second forward put first."""

    best: np.ndarray
    served: np.ndarray
    other: np.ndarray
    argmax: np.ndarray

    @property
    def gap(self) -> np.ndarray:
        return self.best - self.served

    @property
    def other_gap(self) -> np.ndarray:
        return self.best - self.other


def token_logits(params, a: Arch, drop_mask: Sequence[bool], tp: int,
                 seqs: List[np.ndarray], n_served: List[int], *,
                 mode: str = "f32", other: Optional[List[np.ndarray]] = None,
                 device=None) -> List[Reading]:
    """One forward of `mode` over each sequence (prompt + served tokens),
    layer by layer so that only one layer's weights sit on `device`.
    Row j of sequence s reads the logits that predict served token j,
    the token at position len(s) - n_served + j."""
    if tp > 1 and any(drop_mask) and (a.kv_heads % tp or a.ff % tp):
        raise ValueError("the SPD reference needs kv heads and MLP width "
                         f"divisible by tp={tp}")
    device = device or jax.devices()[0]
    put = lambda t: jax.device_put(t, device)
    pads, rows, srv, oth = [], [], [], []
    for i, (s, n) in enumerate(zip(seqs, n_served)):
        sp = -(-len(s) // SEQ_BUCKET) * SEQ_BUCKET
        toks = np.zeros(sp, np.int32)
        toks[:len(s)] = s
        r = np.arange(len(s) - n - 1, len(s) - 1)
        pads.append(toks)
        rows.append(r)
        srv.append(np.asarray(s[len(s) - n:], np.int32))
        oth.append(np.asarray(other[i] if other is not None
                              else srv[-1], np.int32))
    emb = put(params["emb"])
    pos_table = put(params["pos"]) if a.learned_pos else None
    xs = [_embed(emb, pos_table, put(t), a) for t in pads]
    if a.tied:
        head = emb.T
    else:
        del emb
        head = put(params["head"])
    for li in range(a.layers):
        lp = put(params["layers"][li])
        xs = [_layer(lp, x, a, bool(drop_mask[li]), tp, mode) for x in xs]
        del lp
    lnf = put(params["lnf"])
    out = []
    for x, r, sv, ot in zip(xs, rows, srv, oth):
        got = _readout(lnf, head, x, put(r), put(sv), put(ot), a, mode)
        out.append(Reading(*[np.asarray(t) for t in got]))
    return out
