"""Seeded Visual-Road-like frames, a frozen copy of the generator the
program ships (``road_gops``), so that the benchmark's inputs and its
reference cannot change with the program.

A camera's stream is a static textured panorama with moving coloured
cars; frames are yielded one GOP at a time as (T, H, W, 3) uint8.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

CAR_COLORS = {
    "red": (220, 40, 40),
    "blue": (40, 60, 220),
    "green": (40, 200, 60),
    "white": (235, 235, 235),
    "yellow": (230, 210, 40),
}


@dataclasses.dataclass
class Car:
    color_name: str
    row: int
    speed: float  # px / frame
    x0: float
    w: int = 24
    h: int = 12

    def box_at(self, t: int, pan_w: int) -> Tuple[int, int, int, int]:
        x = int(self.x0 + self.speed * t) % pan_w
        return x, self.row, x + self.w, self.row + self.h


def _smooth_noise(rng, h, w, passes=3, k=9) -> np.ndarray:
    x = rng.random((h, w), dtype=np.float32)
    for _ in range(passes):
        c = np.cumsum(x, axis=0)
        x = (np.vstack([c[k:], np.tile(c[-1], (k, 1))]) - c) / k
        c = np.cumsum(x, axis=1)
        x = (np.hstack([c[:, k:], np.tile(c[:, -1:], (1, k))]) - c) / k
    x -= x.min()
    x /= max(x.max(), 1e-6)
    return x


def make_world(rng: np.random.Generator, height: int,
               pan_width: int) -> np.ndarray:
    """Static panorama background (H, Wp, 3) uint8."""
    base = _smooth_noise(rng, height, pan_width)
    sky = np.linspace(1.0, 0.45, height, dtype=np.float32)[:, None]
    img = np.stack(
        [
            90 + 110 * base * sky,
            100 + 100 * base * sky,
            120 + 90 * sky + 20 * base,
        ],
        axis=-1,
    )
    n_buildings = max(4, pan_width // 120)
    for _ in range(n_buildings):
        bw = int(rng.integers(30, 80))
        bh = int(rng.integers(height // 4, height // 2))
        bx = int(rng.integers(0, max(pan_width - bw, 1)))
        by = height // 2 - bh
        shade = float(rng.uniform(30, 90))
        img[by : by + bh, bx : bx + bw] = shade
        for wy in range(by + 4, by + bh - 4, 10):
            for wx in range(bx + 4, bx + bw - 4, 10):
                img[wy : wy + 5, wx : wx + 5] = 200 + 40 * rng.random()
    road_top = int(height * 0.62)
    img[road_top:] = 70
    for lx in range(0, pan_width, 40):
        img[(road_top + height) // 2 - 2 : (road_top + height) // 2,
            lx : lx + 20] = 220
    return np.clip(img, 0, 255).astype(np.uint8)


def make_cars(rng: np.random.Generator, height: int, pan_width: int,
              n_cars: int) -> List[Car]:
    names = list(CAR_COLORS)
    road_top = int(height * 0.62)
    cars = []
    for i in range(n_cars):
        cars.append(Car(
            color_name=names[int(rng.integers(0, len(names)))],
            row=int(rng.integers(road_top + 4, height - 20)),
            speed=float(rng.uniform(1.0, 4.0)) * (1 if i % 2 else -1),
            x0=float(rng.uniform(0, pan_width)),
        ))
    return cars


def render_panorama(world: np.ndarray, cars: List[Car], t: int) -> np.ndarray:
    frame = world.copy()
    h, pan_w, _ = world.shape
    for car in cars:
        x0, y0, x1, y1 = car.box_at(t, pan_w)
        frame[y0:min(y1, h), x0:min(x1, pan_w)] = CAR_COLORS[car.color_name]
    return frame


class Camera:
    """One camera, as ``road_gops(..., seed=seed)`` of the program draws
    it: any range of its frames, rendered on demand (the world and cars
    are drawn once)."""

    def __init__(self, width: int, height: int, seed: int, n_cars: int = 6):
        rng = np.random.default_rng(seed)
        self.world = make_world(rng, height, width)
        self.cars = make_cars(rng, height, width, n_cars)

    def frames(self, start: int, stop: int) -> np.ndarray:
        return np.stack([render_panorama(self.world, self.cars, t)
                         for t in range(start, stop)])


def camera_seed(seed: int, cam: int) -> int:
    """The generator seed of camera ``cam`` in a run seeded ``seed``."""
    return int(seed) * 64 + cam
