"""Single-hall Monte Carlo cells with GPU pods, warmed on grids of their own.

The cells of `bench.adapters.hall`, with one difference in set-up.  The
split-pods scan takes its window bounds as data, so every grid of one
pod mix runs one compiled trial, as with rack-scale GPUs.  Set-up
therefore warms one grid of its own, then runs two more grids of the
mix and checks that they compiled nothing: no compile can reach the
window.  A program that compiles a trial per pod grid cannot run the
cell and is refused there, before any measurement.
"""
from __future__ import annotations

import jax

from bench.adapters import hall

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_GRIDS = 2       # grids run after the warm-up one, which must not compile

_compiles = []


def _on_event(event, duration, **_):
    if event == COMPILE_EVENT:
        _compiles.append(duration)


jax.monitoring.register_event_duration_secs_listener(_on_event)


class Cell(hall.Cell):
    def __init__(self, cell, seed: int, devices, span):
        super().__init__(cell, seed, devices, span)
        self.warm_each_call = False

    def warm(self, i: int):
        self._run(i)
        n = len(_compiles)
        for j in range(1, CHECK_GRIDS + 1):
            self._run(i - j)
        if len(_compiles) > n:
            raise RuntimeError(
                f"{len(_compiles) - n} compilation(s) in {CHECK_GRIDS} "
                "further grids of the pod mix after the warm-up grid: the "
                "program compiles a trial per pod grid, so the window "
                "would compile")


def make(cell, seed, devices, span):
    return Cell(cell, seed, devices, span)
