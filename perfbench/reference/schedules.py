"""Sampler arithmetic of the references, from the published sampler math.

Tables are float64 numpy on the host; a step is plain float32 arithmetic
on the latent. No scan: the callers loop in Python.
"""
from __future__ import annotations

import numpy as np

TRAIN_STEPS = 1000


def sd_alphas_cumprod() -> np.ndarray:
    """Stable Diffusion's 'scaled_linear' betas (0.00085..0.012)."""
    betas = np.linspace(0.00085 ** 0.5, 0.012 ** 0.5, TRAIN_STEPS,
                        dtype=np.float64) ** 2
    return np.cumprod(1.0 - betas)


class DDIM:
    """DDIM, eta 0, epsilon prediction, 'leading' spacing with offset 1,
    final step to alphas_cumprod[0] (set_alpha_to_one False)."""

    def __init__(self, steps: int):
        acp = sd_alphas_cumprod()
        ratio = TRAIN_STEPS // steps
        self.timesteps = (np.arange(steps) * ratio)[::-1] + 1
        prev = self.timesteps - ratio
        self.a_t = acp[self.timesteps]
        self.a_prev = np.where(prev >= 0, acp[np.clip(prev, 0, None)], acp[0])
        self.calls = steps

    def start(self, x):
        return None

    def step(self, i, x, eps, state):
        a_t, a_p = self.a_t[i], self.a_prev[i]
        x0 = (x - np.float32(np.sqrt(1 - a_t)) * eps) \
            / np.float32(np.sqrt(a_t))
        return np.float32(np.sqrt(a_p)) * x0 \
            + np.float32(np.sqrt(1 - a_p)) * eps, state


class DPMSolverMultistep:
    """DPM-Solver++(2M), epsilon prediction, midpoint rule; first order on
    the first step and, under 15 steps, on the last."""

    def __init__(self, steps: int):
        acp = sd_alphas_cumprod()
        ts = np.linspace(0, TRAIN_STEPS - 1, steps + 1).round()[::-1][:-1]
        self.timesteps = ts.astype(np.int64)
        t_all = np.concatenate([self.timesteps, [0]])
        self.alpha = np.sqrt(acp[t_all])
        self.sigma = np.sqrt(1 - acp[t_all])
        self.lam = np.log(self.alpha / self.sigma)
        self.calls = steps
        self.lower_final = steps < 15

    def start(self, x):
        return None

    def step(self, i, x, eps, prev_x0):
        a, s, lam = self.alpha, self.sigma, self.lam
        x0 = (x - np.float32(s[i]) * eps) / np.float32(a[i])
        h = lam[i + 1] - lam[i]
        d = x0
        second = i > 0 and not (self.lower_final and i == self.calls - 1)
        if second:
            r = (lam[i] - lam[i - 1]) / h
            d = x0 + np.float32(0.5 / r) * (x0 - prev_x0)
        x_next = np.float32(s[i + 1] / s[i]) * x \
            - np.float32(a[i + 1] * np.expm1(-h)) * d
        return x_next, x0


SAMPLERS = {"DDIM": DDIM, "DPMSolverMultistep": DPMSolverMultistep}


def prior_schedule(steps: int):
    """The prior's cosine alpha-bar at `steps` times from 999 down to 0."""
    ts = np.linspace(999, 0, steps, dtype=np.float64)
    abar = np.cos((ts / 1000 + 0.008) / 1.008 * np.pi / 2) ** 2
    return ts, abar
