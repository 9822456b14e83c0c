"""What decides ``correct``: numbers held to limits, each printed beside its
limit in every run."""
from __future__ import annotations

import math
from typing import Any, Dict, List


class Check:
    def __init__(self) -> None:
        self.ok = True
        self.lines: List[Dict[str, Any]] = []
        self.notes: Dict[str, Any] = {}

    def hold(self, name: str, value: float, limit: float, detail: str = "") -> bool:
        """``value`` may not pass ``limit`` (and has to be a number)."""
        good = bool(math.isfinite(value) and value <= limit)
        self.lines.append({"name": name, "value": value, "limit": limit,
                           "ok": good, "detail": detail})
        self.ok = self.ok and good
        return good

    def require(self, name: str, holds: bool, detail: str = "") -> bool:
        self.lines.append({"name": name, "value": bool(holds), "limit": True,
                           "ok": bool(holds), "detail": detail})
        self.ok = self.ok and bool(holds)
        return bool(holds)

    def note(self, name: str, value: Any) -> None:
        self.notes[name] = value

    def print(self) -> None:
        for ln in self.lines:
            mark = "ok  " if ln["ok"] else "FAIL"
            print(f"compare {mark} {ln['name']}: {ln['value']!r} (limit {ln['limit']!r})"
                  + (f"  [{ln['detail']}]" if ln["detail"] else ""), flush=True)
        for k, v in self.notes.items():
            print(f"note {k}: {v!r}", flush=True)
