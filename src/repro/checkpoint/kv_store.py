"""KV checkpoint store: cadenced snapshots of the serving engine's live KV.

Where :mod:`repro.checkpoint.store` checkpoints *model parameters* for the
training restart path, this store checkpoints *decode continuations*: for
every request resident in a slot, the backend state needed to resume its
stream (the KV pages / recurrent state), the last emitted token, and how
many tokens had been emitted at snapshot time.  When a host dies
mid-decode, its residents' HBM pages vanish — the engine then restores each
orphan either from the newest snapshot here (pay the per-byte transfer toll
plus a short replay of the tokens emitted since the snapshot) or by
re-prefilling from scratch, whichever the cost model quotes cheaper.

The on-disk discipline mirrors ``store.py`` exactly — one directory per
snapshot step, written into a ``.tmp_step_*`` dir and ``os.replace``'d into
place, with a ``manifest.json`` recording every entry — so a crash mid-write
never corrupts the newest complete snapshot and ``latest_step`` semantics
are shared.  Unlike ``store.py`` it restores without a ``like`` tree: each
entry's state is an arbitrary nested tuple/list/dict pytree of arrays, and
the manifest records the structure.  The module is numpy-only so the stub
engine (and tier-1 CI) never pays a jax import for elasticity tests.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from pathlib import Path
from typing import Any, Optional

import ml_dtypes
import numpy as np

_BF16 = "bfloat16"


@dataclasses.dataclass
class KVSnapshot:
    """One restorable continuation: resume ``rid`` by feeding ``tok`` (its
    ``emitted``-th output token) to a backend holding ``state``."""
    rid: int
    state: Any
    tok: int
    emitted: int


def _encode(node, files: list, prefix: str):
    """Recursively encode a state pytree: arrays become npy files, structure
    becomes a JSON spec.  Returns the spec."""
    if isinstance(node, dict):
        keys = sorted(node)
        return {"t": "dict", "keys": keys,
                "items": [_encode(node[k], files, f"{prefix}_{i}")
                          for i, k in enumerate(keys)]}
    if isinstance(node, (list, tuple)):
        return {"t": "list" if isinstance(node, list) else "tuple",
                "items": [_encode(v, files, f"{prefix}_{i}")
                          for i, v in enumerate(node)]}
    arr = np.asarray(node)
    dtype = str(arr.dtype)
    if dtype == _BF16:                   # ml_dtypes leaf via a jax backend
        arr = arr.view(np.uint16)
    fn = f"{prefix}.npy"
    files.append((fn, arr))
    return {"t": "arr", "file": fn, "dtype": dtype}


def _decode(spec, dirpath: Path):
    if spec["t"] == "dict":
        return {k: _decode(s, dirpath)
                for k, s in zip(spec["keys"], spec["items"])}
    if spec["t"] in ("list", "tuple"):
        items = [_decode(s, dirpath) for s in spec["items"]]
        return items if spec["t"] == "list" else tuple(items)
    arr = np.load(dirpath / spec["file"])
    if spec["dtype"] == _BF16:
        arr = arr.view(ml_dtypes.bfloat16)
    return arr


def latest_step(dirpath: str | Path) -> Optional[int]:
    """Newest complete snapshot step, ignoring in-flight ``.tmp_step_*``
    dirs and directories whose manifest never landed."""
    dirpath = Path(dirpath)
    if not dirpath.exists():
        return None
    best = None
    for d in dirpath.iterdir():
        if d.name.startswith("step_") and (d / "manifest.json").exists():
            s = int(d.name.split("_")[1])
            best = s if best is None else max(best, s)
    return best


class KVStore:
    """Cadenced snapshot writer + restorer for decode continuations.

    ``maybe_snapshot(step, entries)`` is called every engine step; it
    writes at most once per ``cadence`` steps.  ``entries`` maps
    ``rid -> (state, tok, emitted)``.  Restore gives back
    ``{rid: KVSnapshot}`` from the newest complete snapshot.
    """

    def __init__(self, dirpath: str | Path, cadence: int = 8):
        assert cadence >= 1
        self.dirpath = Path(dirpath)
        self.cadence = cadence
        self._last: Optional[int] = None

    def due(self, step: int) -> bool:
        """Whether the cadence calls for a snapshot at ``step`` — cheap,
        so callers can skip gathering entries on off-cadence steps."""
        return self._last is None or step - self._last >= self.cadence

    def maybe_snapshot(self, step: int, entries: dict) -> bool:
        if not self.due(step):
            return False
        self.snapshot(step, entries)
        return True

    def snapshot(self, step: int, entries: dict) -> Path:
        """Unconditional atomic snapshot write (tmp dir + rename)."""
        final = self.dirpath / f"step_{step:08d}"
        tmp = self.dirpath / f".tmp_step_{step:08d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        files: list[tuple[str, np.ndarray]] = []
        manifest = {"step": step, "entries": {}}
        for rid, (state, tok, emitted) in entries.items():
            spec = _encode(state, files, f"r{rid}")
            manifest["entries"][str(rid)] = {
                "tok": int(tok), "emitted": int(emitted), "spec": spec}
        for fn, arr in files:
            np.save(tmp / fn, arr)
        (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._last = step
        return final

    def latest(self) -> Optional[int]:
        return latest_step(self.dirpath)

    def restore(self, step: Optional[int] = None) -> dict[int, KVSnapshot]:
        """``{rid: KVSnapshot}`` from ``step`` (default: newest complete).
        An empty dict when no snapshot exists — the caller then quotes only
        the re-prefill path."""
        if step is None:
            step = self.latest()
        if step is None:
            return {}
        final = self.dirpath / f"step_{step:08d}"
        manifest = json.loads((final / "manifest.json").read_text())
        out: dict[int, KVSnapshot] = {}
        for rid_s, info in manifest["entries"].items():
            rid = int(rid_s)
            out[rid] = KVSnapshot(rid=rid,
                                  state=_decode(info["spec"], final),
                                  tok=info["tok"], emitted=info["emitted"])
        return out
