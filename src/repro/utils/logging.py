"""Tiny structured logger (stdout + optional jsonl file). Timing spans live
in ``repro.utils.trace``."""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Optional


class MetricLogger:
    def __init__(self, jsonl_path: Optional[str] = None, quiet: bool = False):
        self.jsonl_path = jsonl_path
        self.quiet = quiet
        self._t0 = time.perf_counter()
        if jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)), exist_ok=True)
            # truncate
            open(jsonl_path, "w").close()

    def log(self, step: int, **metrics: Any) -> None:
        rec = {"step": step, "t": round(time.perf_counter() - self._t0, 3), **metrics}
        if not self.quiet:
            parts = " ".join(
                f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in metrics.items()
            )
            print(f"[step {step:>5}] {parts}", file=sys.stderr)
        if self.jsonl_path:
            with open(self.jsonl_path, "a") as f:
                f.write(json.dumps(rec, default=float) + "\n")
