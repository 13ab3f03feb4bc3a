"""Deltas of the program's metric registry over the measured window.

The program counts at its own boundaries (``serve/batching.py:
_engine_metrics``); the benchmark reads the families by name at the
window's two ends and, for gauges, samples them at a fixed period.
Histogram buckets are too coarse for a percentile: only sum and count
are read."""
import threading
from typing import Dict, List, Optional, Tuple


def _snapshot(registry) -> Dict[str, Tuple[float, float]]:
    """name -> (sum, count) for histograms, (value, 0) otherwise.
    Labelled families sum over their children."""
    out: Dict[str, Tuple[float, float]] = {}
    for fam in registry.families():
        total, count = 0.0, 0.0
        for _, child in fam.collect():
            if fam.kind == 'histogram':
                _, s, c = child.snapshot()
                total += s
                count += c
            else:
                total += child.value
        out[fam.name] = (total, count)
    return out


class RegistryWindow:
    """Counter/histogram deltas between ``open()`` and ``close()``,
    plus gauge samples taken every ``period_s`` in between."""

    def __init__(self, registry, gauges: List[str],
                 period_s: float = 0.05):
        self._registry = registry
        self._gauges = list(gauges)
        self._period = period_s
        self._before: Dict[str, Tuple[float, float]] = {}
        self._after: Dict[str, Tuple[float, float]] = {}
        self.samples: Dict[str, List[float]] = {g: [] for g in gauges}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _sample_loop(self) -> None:
        by_name = {f.name: f for f in self._registry.families()}
        while not self._stop.wait(self._period):
            for g in self._gauges:
                fam = by_name.get(g)
                if fam is not None:
                    self.samples[g].append(fam.value)

    def open(self) -> None:
        self._before = _snapshot(self._registry)
        self._thread = threading.Thread(target=self._sample_loop,
                                        daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._after = _snapshot(self._registry)

    def delta(self, name: str) -> Optional[Tuple[float, float]]:
        """(sum delta, count delta) of a family over the window, or
        None where the program has no such family."""
        if name not in self._after:
            return None
        b = self._before.get(name, (0.0, 0.0))
        a = self._after[name]
        return a[0] - b[0], a[1] - b[1]

    def last(self, name: str) -> Optional[float]:
        return self._after[name][0] if name in self._after else None
