"""Traffic: synthetic camera footage and the open-loop arrival schedule.

One general generator reads a traffic file (``bench/traffic/<name>.json``)
and makes, from ``--seed``:

- per camera, a pool of whole hopping windows of frames.  Objects are
  born, persist, move and bounce on the filter's patch grid with the
  scene's statistics (paper Table II: classes and their skew, objects per
  frame), and each frame is rendered to patch embeddings (background +
  class prototypes of the objects in a cell + noise), the stub frontend
  the filter reads.  The dynamics follow ``repro.data.synthetic``; the
  rendering runs on the device in one jitted call per camera, and the
  pool is then kept in host memory, where a camera's frames (live) or a
  recording (archive) are before the served path moves them to the
  device;
- the arrival schedule.  ``live``: each camera makes frames at a fixed
  rate, start phases spread over one frame interval, and a frame exists
  from its creation stamp on.  ``archive``: all recorded frames exist at
  the start.

Every seed gets the same sizes: the same cameras, pool length, objects
statistics and schedule; only the draws differ.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.seeds import device_key, host_rng


@dataclasses.dataclass(frozen=True)
class Scene:
    """A scene's statistics (one traffic file's ``scene`` object)."""
    name: str
    class_probs: tuple
    mean_objects: float
    std_objects: float
    persistence: float
    speed_cells: float        # cells per frame on the filter's grid
    d_embed: int
    noise: float

    @property
    def n_classes(self) -> int:
        return len(self.class_probs)

    @classmethod
    def from_json(cls, d: dict) -> "Scene":
        return cls(name=d["name"], class_probs=tuple(d["class_probs"]),
                   mean_objects=float(d["mean_objects"]),
                   std_objects=float(d["std_objects"]),
                   persistence=float(d["persistence"]),
                   speed_cells=float(d["speed_cells"]),
                   d_embed=int(d["d_embed"]), noise=float(d["noise"]))


def simulate_objects(scene: Scene, grid: int, n_frames: int,
                     rng: np.random.Generator, warmup: int = 50
                     ) -> List[np.ndarray]:
    """Per frame, an (N, 3) int64 table of (class, row, col) objects."""
    birth = max(scene.mean_objects * (1 - scene.persistence)
                - 0.02 * scene.std_objects, 0.01)
    probs = np.asarray(scene.class_probs) / np.sum(scene.class_probs)
    obj = np.zeros((0, 5))            # class, row, col, v_row, v_col
    out = []
    for t in range(warmup + n_frames):
        if len(obj):
            obj = obj[rng.random(len(obj)) < scene.persistence]
            obj[:, 1:3] += obj[:, 3:5]
            for d in (1, 2):          # bounce at the borders
                lo, hi = obj[:, d] < 0, obj[:, d] > grid - 1
                obj[lo, d] = -obj[lo, d]
                obj[hi, d] = 2 * (grid - 1) - obj[hi, d]
                obj[lo | hi, d + 2] *= -1
        n_new = rng.poisson(birth)
        if rng.random() < 0.02:       # group arrivals give the spread
            n_new += rng.poisson(scene.std_objects)
        if n_new:
            cls = rng.choice(len(probs), n_new, p=probs)
            pos = rng.uniform(0, grid - 1, (n_new, 2))
            vel = rng.normal(0, scene.speed_cells, (n_new, 2))
            obj = np.concatenate(
                [obj, np.column_stack([cls.astype(float), pos, vel])], 0)
        if t >= warmup:
            rc = np.clip(np.round(obj[:, 1:3]), 0, grid - 1)
            out.append(np.column_stack([obj[:, 0], rc]).astype(np.int64)
                       .reshape(-1, 3))
    return out


def _render(key, background, protos, occupancy, noise):
    """(F, g2, C) object counts per cell -> (F, g2, D) f32 embeddings."""
    emb = background[None] + jnp.einsum("fpc,cd->fpd", occupancy, protos,
                                        precision="highest")
    return emb + noise * jax.random.normal(key, emb.shape, jnp.float32)


_render_jit = jax.jit(_render)


@dataclasses.dataclass
class Footage:
    """What the cameras hold: per camera, the frame pool in host memory
    and the ground-truth objects of each pool frame."""
    pools: List[np.ndarray]             # per camera (F, g2, D) f32
    objects: List[List[np.ndarray]]     # per camera, per pool frame
    pool_frames: int


def make_footage(scene: Scene, grid: int, n_cameras: int, pool_frames: int,
                 seed: int) -> Footage:
    """Every camera's pool, from the seed (world: prototypes and
    background; dynamics and noise: per camera)."""
    world = host_rng(seed, "world")
    protos = jnp.asarray(world.normal(0, 1, (scene.n_classes, scene.d_embed))
                         .astype(np.float32))
    background = jnp.asarray(world.normal(0, 0.2, (grid * grid,
                                                   scene.d_embed))
                             .astype(np.float32))
    pools, objects = [], []
    for cam in range(n_cameras):
        objs = simulate_objects(scene, grid, pool_frames,
                                host_rng(seed, "camera", cam))
        occ = np.zeros((pool_frames, grid * grid, scene.n_classes),
                       np.float32)
        for f, o in enumerate(objs):
            np.add.at(occ[f], (o[:, 1] * grid + o[:, 2], o[:, 0]), 1.0)
        pools.append(np.asarray(_render_jit(
            device_key(seed, "noise", cam), background, protos,
            jnp.asarray(occ), jnp.float32(scene.noise))))
        objects.append(objs)
    return Footage(pools=pools, objects=objects, pool_frames=pool_frames)


class Schedule:
    """When each camera's frames exist (host clock, ``time.perf_counter``).

    ``rate_fps`` is the fleet's offered rate, split evenly over the
    cameras; ``None`` is an archive: every frame exists from ``t0`` on."""

    def __init__(self, n_cameras: int, rate_fps: Optional[float]):
        self.n_cameras = n_cameras
        self.rate_fps = rate_fps
        self.t0 = 0.0
        self.late_s: List[float] = []     # how late each wait woke up

    @property
    def live(self) -> bool:
        return self.rate_fps is not None

    def start(self, t0: float) -> None:
        self.t0 = t0
        self.late_s = []

    def stamp(self, cam: int, frame: int) -> float:
        """Creation time of a camera's frame."""
        if not self.live:
            return self.t0
        per_cam = self.rate_fps / self.n_cameras
        return self.t0 + (frame + cam / self.n_cameras) / per_cam

    def wait_for(self, cam: int, frame: int) -> float:
        """Block until the frame exists; returns the seconds waited."""
        due = self.stamp(cam, frame)
        now = time.perf_counter()
        if now >= due:
            return 0.0
        while True:
            left = due - time.perf_counter()
            if left <= 0:
                break
            time.sleep(min(left, 0.002) if left < 0.004 else left - 0.002)
        woke = time.perf_counter()
        self.late_s.append(woke - due)
        return woke - now
