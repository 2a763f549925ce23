"""Training + evaluation of the paper's filter branches (§II, §IV).

The filter model = input projection (stub-frontend width -> d_model)
+ the first k trunk layers of a backbone (shared with the oracle, per the
paper) + a branch head (IC / OD / OD-COF).  Trained on synthetic video
streams with the paper's losses (Eq. 2 for IC, Eq. 3 for OD) and the
paper's optimizers (§IV: Adam lr 1e-4 + exp decay for IC; SGD momentum
0.9 for OD), then evaluated with the paper's metrics:

- count accuracy at tolerance 0/1/2 (Fig. 7 / Fig. 11)
- per-class localisation f1 at Manhattan radius 0/1/2 (Fig. 15)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cam as CAM
from repro.core import filters as F
from repro.data.synthetic import SceneConfig, VideoStream, collect, class_weights
from repro.models import model as M
from repro.models.config import BranchSpec, ModelConfig
from repro.models.layers import dense_init
from repro.optim import (adamw, sgd_momentum, exponential_decay,
                         clip_by_global_norm)
from repro.optim.optimizers import apply_updates

Params = Dict[str, Any]


def default_trunk(d_model: int = 128, n_layers: int = 4,
                  grid: int = 8) -> ModelConfig:
    """Small bidirectional trunk for the filter (the 'VGG-prefix' analog)."""
    return ModelConfig(
        name="filter-trunk", n_layers=n_layers, d_model=d_model,
        n_heads=4, n_kv_heads=4, head_dim=d_model // 4, d_ff=4 * d_model,
        vocab_size=32, dtype="float32", use_rope=False,
        max_seq_len=grid * grid + 8, attn_impl="xla_naive")


def init_filter_model(rng, trunk_cfg: ModelConfig, spec: BranchSpec,
                      d_in: int) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "proj": dense_init(k1, d_in, (d_in, trunk_cfg.d_model), jnp.float32),
        "pos": (jax.random.normal(k2, (spec.grid * spec.grid + 8,
                                       trunk_cfg.d_model)) * 0.02),
        "trunk": M.init_params(k3, trunk_cfg),
        "branch": F.branch_init(k2, spec, trunk_cfg.d_model),
    }


def filter_tap(p: Params, trunk_cfg: ModelConfig, spec: BranchSpec,
               embeds: jax.Array) -> jax.Array:
    """embeds: (B, P, d_in) stub-frontend patches -> the trunk activations
    after layer ``spec.layer`` (B, P, d_model), which the branch consumes."""
    x = jnp.einsum("bpd,de->bpe", embeds.astype(jnp.float32), p["proj"])
    x = x + p["pos"][: x.shape[1]][None]
    return M.forward(p["trunk"], trunk_cfg, tokens=None, embeds=x,
                     tap_layer=spec.layer, stop_at_tap=True,
                     causal=False).tap


def filter_forward(p: Params, trunk_cfg: ModelConfig, spec: BranchSpec,
                   embeds: jax.Array, use_kernel: bool = False
                   ) -> F.FilterOutputs:
    """embeds: (B, P, d_in) stub-frontend patches -> FilterOutputs."""
    tap = filter_tap(p, trunk_cfg, spec, embeds)
    return F.branch_apply(p["branch"], tap, spec,
                          **({"use_kernel": use_kernel}
                             if spec.kind == "ic" else {}))


@dataclasses.dataclass
class TrainedFilter:
    params: Params
    trunk_cfg: ModelConfig
    spec: BranchSpec
    losses: list
    count_scale: np.ndarray = None   # per-class target normalisation

    def _rescale(self, out: F.FilterOutputs) -> F.FilterOutputs:
        if self.count_scale is None:
            return out
        return F.FilterOutputs(counts=out.counts *
                               jnp.asarray(self.count_scale), grid=out.grid)

    def apply(self, embeds) -> F.FilterOutputs:
        return self._rescale(
            filter_forward(self.params, self.trunk_cfg, self.spec, embeds))

    def jitted(self) -> Callable:
        cfg, spec = self.trunk_cfg, self.spec
        scale = (jnp.asarray(self.count_scale)
                 if self.count_scale is not None else None)

        def fn(p, e):
            out = filter_forward(p, cfg, spec, e)
            if scale is not None:
                out = F.FilterOutputs(counts=out.counts * scale,
                                      grid=out.grid)
            return out
        return jax.jit(fn)


def train_filter(scene: SceneConfig, spec: BranchSpec, *,
                 trunk_cfg: Optional[ModelConfig] = None,
                 steps: int = 300, batch: int = 32,
                 n_frames: int = 2048, seed: int = 0,
                 log_every: int = 0) -> TrainedFilter:
    """End-to-end branch training on a synthetic stream (paper §IV setup)."""
    trunk_cfg = trunk_cfg or default_trunk(grid=scene.grid)
    spec = dataclasses.replace(spec, grid=scene.grid,
                               n_classes=scene.n_classes)
    rng = jax.random.PRNGKey(seed)
    params = init_filter_model(rng, trunk_cfg, spec, scene.d_embed)

    data = collect(VideoStream(scene), n_frames)
    w_c = jnp.asarray(class_weights(data["counts"]))
    embeds = jnp.asarray(data["embeds"])
    # normalise count targets to ~unit scale per class (high-count scenes
    # like coral/detrac otherwise sit far outside the head's init range)
    count_scale = np.maximum(data["counts"].mean(0), 1.0).astype(np.float32)
    counts = jnp.asarray(data["counts"] / count_scale)
    occ = jnp.asarray(data["occupancy"], jnp.float32)

    # Paper §IV trains IC with Adam and OD with small-lr SGD+momentum
    # ("unstable gradients at the added branch").  At our compressed CPU
    # step budgets SGD either diverges (large lr) or undertrains (their
    # 1e-4), so both branches use Adam + global-norm clipping; the paper's
    # exponential weight decay (5e-4) is kept.  Recorded in EXPERIMENTS.md.
    if spec.kind == "ic":
        opt = adamw(exponential_decay(1e-3, 5e-4))
    else:
        opt = adamw(exponential_decay(2e-3, 5e-4))
    opt_state = opt.init(params)
    clip = clip_by_global_norm(1.0)

    # Loss balance "set manually based on the training set" (paper §IV):
    # scale the grid term by inverse occupied-cell density so sparse scenes
    # (jackson, ~1% positives) keep a strong localisation gradient while
    # dense scenes (coral, ~14%) don't starve the count head.
    pos_density = float(np.asarray(occ).mean())
    lam_grid = 20.0 * min(1.0, 0.02 / max(pos_density, 1e-3))

    def loss_fn(p, e, c, o, beta):
        out = filter_forward(p, trunk_cfg, spec, e)
        if spec.kind == "ic":
            # Eq. 2 schedule: count-only first, then add localisation
            return F.ic_loss(out, c, o, w_c, alpha=1.0,
                             beta=beta * lam_grid / 20.0)
        if spec.kind == "od":
            return F.od_loss(out, c, o, lambda_grid=lam_grid)
        return F.cof_loss(out, c)

    @jax.jit
    def train_step(p, st, step, e, c, o, beta):
        loss, g = jax.value_and_grad(loss_fn)(p, e, c, o, beta)
        g, _ = clip(g)
        upd, st = opt.update(g, st, p, step)
        return apply_updates(p, upd), st, loss

    n = embeds.shape[0]
    losses = []
    key = rng
    warm = max(steps // 6, 1)        # paper: beta=0 for first epochs
    for i in range(steps):
        key, sub = jax.random.split(key)
        idx = jax.random.randint(sub, (batch,), 0, n)
        beta = jnp.float32(0.0 if i < warm else
                           10.0 * max(0.2, 1.0 - (i - warm) / steps))
        params, opt_state, loss = train_step(
            params, opt_state, jnp.int32(i), embeds[idx], counts[idx],
            occ[idx], beta)
        losses.append(float(loss))
        if log_every and i % log_every == 0:
            print(f"  step {i:4d} loss {losses[-1]:.4f}", flush=True)
    return TrainedFilter(params=params, trunk_cfg=trunk_cfg, spec=spec,
                         losses=losses, count_scale=count_scale)


# --------------------------------------------------------------------------
# Paper metrics
# --------------------------------------------------------------------------

def count_accuracy(pred_counts: np.ndarray, true_counts: np.ndarray,
                   tolerance: int = 0, per_class: bool = False):
    """Fig. 7 / Fig. 11 metric: fraction of frames with |c_hat - c| <= tol.

    Total-count version compares summed counts; per-class compares each."""
    p = np.round(np.asarray(pred_counts))
    t = np.asarray(true_counts)
    if per_class:
        return (np.abs(p - t) <= tolerance).mean(0)       # (C,)
    return float((np.abs(p.sum(-1) - t.sum(-1)) <= tolerance).mean())


def clf_f1(grid_logits: np.ndarray, occupancy: np.ndarray,
           tau: float = 0.2, radius: int = 0) -> np.ndarray:
    """Fig. 15 metric: per-class f1 of cell occupancy prediction, counting
    a prediction correct if a true object lies within Manhattan ``radius``."""
    pred = np.asarray(grid_logits) > tau        # raw-value threshold
    true = np.asarray(occupancy) > 0.5
    if radius:
        true_d = np.asarray(CAM.dilate_manhattan(jnp.asarray(true), radius))
        pred_d = np.asarray(CAM.dilate_manhattan(jnp.asarray(pred), radius))
    else:
        true_d, pred_d = true, pred
    C = pred.shape[-1]
    out = np.zeros(C)
    for c in range(C):
        tp = (pred[..., c] & true_d[..., c]).sum()
        fp = (pred[..., c] & ~true_d[..., c]).sum()
        fn = (true[..., c] & ~pred_d[..., c]).sum()
        prec = tp / max(tp + fp, 1)
        rec = tp / max(tp + fn, 1)
        out[c] = 2 * prec * rec / max(prec + rec, 1e-9)
    return out


def evaluate_filter(tf: TrainedFilter, scene: SceneConfig,
                    n_frames: int = 512, seed: int = 99) -> Dict[str, Any]:
    # same camera/world (protos, background), held-out dynamics
    data = collect(VideoStream(scene, dynamics_seed=seed), n_frames)
    fn = tf.jitted()
    out = fn(tf.params, jnp.asarray(data["embeds"]))
    res: Dict[str, Any] = {"counts_pred": np.asarray(out.counts)}
    for tol in (0, 1, 2):
        res[f"cf_acc_{tol}"] = count_accuracy(out.counts, data["counts"], tol)
        res[f"ccf_acc_{tol}"] = count_accuracy(out.counts, data["counts"],
                                               tol, per_class=True)
    if out.grid is not None:
        for r in (0, 1, 2):
            res[f"clf_f1_{r}"] = clf_f1(out.grid, data["occupancy"],
                                        radius=r)
    res["data"] = data
    res["outputs"] = out
    return res
