"""Prometheus text exposition -> {(name, sorted label items): value}.
Copied from infinistore_tpu.utils.metrics.parse_prometheus_text so that the
yardstick does not move with the program."""

from __future__ import annotations

from typing import Dict, Tuple

Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def parse(text: str) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            metric, value = line.rsplit(" ", 1)
            labels: Tuple[Tuple[str, str], ...] = ()
            name = metric
            if "{" in metric:
                name, rest = metric.split("{", 1)
                items = []
                for pair in rest.rsplit("}", 1)[0].split(","):
                    if pair:
                        k, v = pair.split("=", 1)
                        items.append((k, v.strip('"')))
                labels = tuple(sorted(items))
            out[(name, labels)] = float(value)
        except ValueError:
            continue
    return out


def family(metrics: Dict[Key, float], name: str) -> Dict[str, float]:
    """{first label's value: sample} of a labelled family."""
    return {labels[0][1]: v for (n, labels), v in metrics.items()
            if n == name and labels}


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: after.get(k, 0.0) - before.get(k, 0.0)
            for k in set(after) | set(before)}
