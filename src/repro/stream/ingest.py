"""The :class:`Ingestor`: applies :class:`~repro.stream.source.DataSource`
deltas to a running job at host-synced chunk boundaries.

The engine's chunked execution loop is the only place model state and
data are host-visible between compiled spans — the partitioner already
rebalances there, checkpoints already save there, the serve loop already
publishes there.  The Ingestor rides the same boundaries:

* ``"replace"`` overwrites the row slots each delta names, then
  re-places **only the changed leaves** with per-leaf ``device_put``
  (never a full ``shard_data`` rebuild — unchanged leaves are returned
  by the app's ``ingest()`` as the *same objects* and are left alone);
* ``"extend"`` appends rows as if one at a time into a capacity-padded
  ring buffer: new rows land in the padding slots first (the app's
  ``ingest_specs()["valid"]`` mask says which slots hold real rows at
  bind time), then wrap around and overwrite the oldest rows.  Data
  shapes never change, so the compiled round programs are reused — not
  recompiled (asserted in ``tests/test_stream.py``).

The cursor (``cursor``/``rows_in``/``rows_dropped``/``fill0``) is plain
flat numpy and rides the checkpoint payload beside ``"state"`` /
``"carry"`` / ``"assignment"``, so a mid-stream checkpoint resumes
bit-exactly: restore it with ``execute(..., stream_state=...)`` and
rebuild the data a resumed process no longer holds with
:func:`replay_data`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from .source import _delta_rows
from .spec import StreamSpec

_CURSOR_KEYS = ("cursor", "rows_in", "rows_dropped", "fill0")


def _place_changed(engine, old, new, specs):
    """Re-place only the leaves ``ingest()`` actually replaced (identity
    compare — the unchanged-leaves-are-the-same-objects contract)."""
    return jax.tree.map(
        lambda o, n, s: o if n is o else jax.device_put(
            n, NamedSharding(engine.mesh, s)),
        old, new, specs)


def _slice_delta(delta: dict, keep: int) -> dict:
    """The last ``keep`` rows of every per-row array in a delta."""
    k = _delta_rows(delta)
    if keep >= k:
        return delta
    out = {}
    for key, val in delta.items():
        if key == "data":
            out[key] = {leaf: v[k - keep:] for leaf, v in val.items()}
        elif key == "rows":
            out[key] = np.asarray(val)[k - keep:]
        else:
            out[key] = np.asarray(val)[k - keep:]
    return out


class Ingestor:
    """Binds a (:class:`StreamSpec`, :class:`DataSource`) pair to one
    engine + data pytree and applies deltas at boundaries."""

    def __init__(self, spec: StreamSpec, source):
        if not isinstance(spec, StreamSpec):
            raise TypeError(f"stream= wants a StreamSpec; "
                            f"got {type(spec).__name__}")
        if not callable(getattr(source, "take", None)):
            raise TypeError(f"source= wants a DataSource (peek/take); "
                            f"got {type(source).__name__}")
        self.spec = spec
        self.source = source
        self.cursor = 0        # extend: rows ever offered to the ring
        self.rows_in = 0       # rows actually written into the buffer
        self.rows_dropped = 0  # delta rows that could never land
        self.fill0 = 0         # extend: valid rows at bind time
        self.capacity = 0
        self._leaves: tuple = ()
        self._total_rows = 0
        self._bound = False
        self._restored = False

    # -- lifecycle -----------------------------------------------------------

    def bind(self, engine, data) -> "Ingestor":
        """Resolve the app's ingest primitives against one data pytree
        (row count, streamable leaves, initial ring fill)."""
        from ..core.primitives import StradsAppBase
        app = engine.app
        for prim in ("ingest", "ingest_specs"):
            fn = getattr(type(app), prim, None)
            if fn is None or fn is getattr(StradsAppBase, prim):
                raise NotImplementedError(
                    f"{type(app).__name__} declares no {prim}() primitive "
                    f"— streaming (repro.stream) needs ingest() and "
                    f"ingest_specs(); see the ingest-injection contract "
                    f"in repro.core.primitives")
        kinds = getattr(app, "supported_stream_kinds", None)
        if kinds is not None and self.spec.kind not in kinds:
            raise ValueError(
                f"{type(app).__name__} supports stream kinds {kinds}; "
                f"spec wants {self.spec.kind!r}")
        isp = app.ingest_specs()
        self._leaves = tuple(isp["leaves"])
        self._total_rows = int(data[self._leaves[0]].shape[0])
        if self.spec.capacity > self._total_rows:
            raise ValueError(
                f"capacity={self.spec.capacity} exceeds the data's "
                f"{self._total_rows} rows")
        self.capacity = self.spec.capacity or self._total_rows
        if self.spec.kind == "extend" and not self._restored:
            valid = isp.get("valid")
            self.fill0 = (int(np.asarray(valid(data)).sum())
                          if valid is not None else 0)
        self._bound = True
        return self

    def payload(self) -> dict:
        """The stream cursor as flat numpy — rides the checkpoint
        payload beside ``"state"``/``"carry"``/``"assignment"``."""
        return {k: np.int64(getattr(self, k)) for k in _CURSOR_KEYS}

    def restore(self, payload: dict) -> "Ingestor":
        """Adopt a checkpointed cursor (call before :meth:`bind`, or
        pass ``stream_state=`` to ``execute`` which does both)."""
        missing = [k for k in _CURSOR_KEYS if k not in payload]
        if missing:
            raise ValueError(f"stream payload missing {missing}")
        for k in _CURSOR_KEYS:
            setattr(self, k, int(np.asarray(payload[k])))
        self._restored = True
        return self

    # -- the boundary step ---------------------------------------------------

    def step(self, engine, state, data, t: int):
        """Apply whatever the source has due at boundary ``t``; returns
        the (possibly re-placed) ``(state, data)``.  A no-op — the very
        same objects back, no transfers, no RNG — when ``t`` is off
        cadence or the source has nothing, which is what makes an
        empty-source streamed run bit-identical to an unstreamed one.
        ``state=None`` applies the data-leaf writes only (the
        :func:`replay_data` path)."""
        if not self._bound:
            raise RuntimeError("Ingestor.step before bind()")
        if t % self.spec.ingest_every != 0:
            return state, data
        deltas = self.source.take(t)
        if not deltas:
            return state, data
        if isinstance(deltas, dict):
            deltas = [deltas]
        if state is not None:
            # a state restored from an npz checkpoint arrives as numpy
            # leaves; ingest primitives use functional-update (`.at`)
            # semantics, so lift to jax arrays once at the boundary
            # (a no-op returning the very same objects when the state
            # already lives on device)
            state = jax.tree_util.tree_map(jnp.asarray, state)
        with engine._obs_span("ingest", t=t, deltas=len(deltas)):
            for delta in deltas:
                rows, delta = self._slots(delta)
                if rows.size == 0:
                    continue
                new_data, new_state = engine.app.ingest(
                    data, state, rows, delta)
                data = _place_changed(engine, data, new_data,
                                      engine.data_specs)
                if state is not None:
                    state = _place_changed(engine, state, new_state,
                                           engine._sspec(state))
                engine._obs_event("ingest_rows", t=t,
                                  rows_in=int(rows.size),
                                  rows_dropped=self.rows_dropped)
        return state, data

    def _slots(self, delta: dict):
        """Row slots for one delta (+ the delta, tail-sliced if the
        ring cannot hold all of it), advancing the cursor."""
        k = _delta_rows(delta)
        if k == 0:
            return np.zeros((0,), np.int64), delta
        if self.spec.kind == "replace":
            rows = np.asarray(delta["rows"], np.int64)
            if rows.shape != (k,):
                raise ValueError(
                    f"replace delta rows shape {rows.shape} != ({k},)")
            if np.unique(rows).size != k:
                raise ValueError("replace delta rows must be unique")
            if rows.size and (rows.min() < 0
                              or rows.max() >= self._total_rows):
                raise ValueError(
                    f"replace delta rows out of range [0, "
                    f"{self._total_rows})")
            self.rows_in += k
            return rows, delta
        # extend: append as if row-by-row; a delta larger than the ring
        # keeps only its last `capacity` rows (the earlier ones would be
        # overwritten before the next round ever saw them)
        keep = min(k, self.capacity)
        dropped = k - keep
        start = self.fill0 + self.cursor + dropped
        rows = (start + np.arange(keep, dtype=np.int64)) % self.capacity
        self.cursor += k
        self.rows_in += keep
        self.rows_dropped += dropped
        return rows, _slice_delta(delta, keep)


def replay_data(engine, data, spec: StreamSpec, source,
                t_upto: int, stream_state: Optional[dict] = None):
    """Rebuild the data pytree a resumed process no longer holds:
    re-apply every boundary ``t < t_upto`` of a deterministic source to
    the *original* data (data-only — derived state comes from the
    checkpoint, never double-applied).  Returns ``(data, ingestor)``;
    the ingestor's cursor equals the checkpointed ``"stream"`` payload
    (pass it as ``stream_state=`` to verify)."""
    ing = Ingestor(spec, source).bind(engine, data)
    for t in range(0, t_upto, spec.ingest_every):
        _, data = ing.step(engine, None, data, t)
    if stream_state is not None:
        got, want = ing.payload(), stream_state
        for key in _CURSOR_KEYS:
            if int(np.asarray(want[key])) != int(got[key]):
                raise ValueError(
                    f"replayed stream cursor {key}={int(got[key])} != "
                    f"checkpointed {int(np.asarray(want[key]))} (source "
                    f"or t_upto does not match the original run)")
    return data, ing
