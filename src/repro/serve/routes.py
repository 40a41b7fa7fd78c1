"""Progressive fidelity-tier routes over stored serve responses.

The response side of the layered serve core (DESIGN.md §14).  The
engine ships each micro-batch as ONE WZRC container; this module is
what a response endpoint does with that stored blob afterwards:

    thumbnail(uid)        the LL/approx band for one request — decodes
                          the header plus a single band blob out of the
                          container (``codec.decode_lowband``), no
                          inverse transform
    refine(uid, L)        the request reconstructed from the coarsest
                          L detail levels — each step up doubles the
                          resolution per axis, reading only the newly
                          needed byte ranges
    full(uid)             ``refine`` at the container's full level
                          count: the original samples, bit-exact

Every tier decodes from byte ranges of the SAME stored bitstream — the
store never re-encodes, never holds per-tier copies, and a client that
stops at the thumbnail never causes the refinement bytes to be read
(``codec.CountingReader`` proves this in the tests).  Batch containers
need no special casing: every band decodes to ``(B, ...)`` and the
route slices the request's recorded ``batch_index`` row.

Tier geometry for padded requests: a request admitted by zero-padding
reconstructs at tier ``L`` to the BUCKET's level-``(levels-L)`` shape;
the route crops to the request's own ceil-halved shape
(``ceil(orig / 2**(levels-L))`` per axis — the lifting split sizes), so
thumbnails of padded requests carry no padding margin.

Each tier call is one root span, ``serve.read`` (attributes ``uid`` and
``tier``, the level count the answer holds), over the per-band
``codec.decode_band`` spans and the ``codec.inverse`` dispatch; the
block on the inverse's result adds to its ``wait_s``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.codec import progressive
from repro.serve.engine import TransformRequest

Shape = Tuple[int, ...]


class StoredResponse(NamedTuple):
    """One request's handle into a stored (possibly shared) container."""

    source: Any  # bytes or a pread() source for the WZRC container
    batch_index: Optional[int]  # row in a batch container; None = whole blob
    image_shape: Shape  # the request's ORIGINAL (pre-padding) shape


def tier_shape(image_shape: Shape, levels: int, up_to_level: int) -> Shape:
    """A request's shape at fidelity tier ``up_to_level``.

    Repeated ceil-halving of the original shape, ``levels - up_to_level``
    times — exactly the lifting cascade's approx sizes, so the crop
    matches the band geometry of an unpadded encode.
    """
    if not 0 <= up_to_level <= levels:
        raise ValueError(
            f"up_to_level must be in [0, {levels}], got {up_to_level}"
        )
    k = levels - up_to_level
    return tuple(-(-s // (1 << k)) for s in image_shape)


@dataclass
class ProgressiveServeRoute:
    """Fidelity-tier responses from one stored bitstream per batch.

    ``store(req)`` files a served request's container handle;
    ``thumbnail`` / ``refine`` / ``full`` answer later fetches at any
    fidelity, each reading only the byte ranges its tier needs.  The
    ``heal``/``partial`` knobs pass through to ``codec.progressive``:
    a damaged refinement band can be healed from parity, quarantined
    zero-filled (``partial=True``), or raised — and never disturbs the
    coarser tiers either way.
    """

    backend: Optional[str] = None
    _store: Dict[int, StoredResponse] = field(default_factory=dict)

    def store(self, req: TransformRequest) -> int:
        """File a served request's encoded response; returns its uid."""
        if req.encoded is None:
            raise ValueError(
                f"request {req.uid} has no encoded response "
                "(engine needs encode_response=True)"
            )
        self._store[req.uid] = StoredResponse(
            source=req.encoded,
            batch_index=req.batch_index,
            image_shape=tuple(req.image.shape),
        )
        return req.uid

    def put(
        self,
        uid: int,
        source: Any,
        *,
        batch_index: Optional[int] = None,
        image_shape: Optional[Shape] = None,
    ) -> None:
        """File a container handle directly (bytes or a pread source)."""
        if image_shape is None:
            h = progressive.read_header(source)
            image_shape = h.shape
        self._store[uid] = StoredResponse(source, batch_index, tuple(image_shape))

    def _entry(self, uid: int) -> StoredResponse:
        try:
            return self._store[uid]
        except KeyError:
            raise KeyError(f"no stored response for request {uid}") from None

    def _row(self, arr, entry: StoredResponse) -> np.ndarray:
        with obs.waiting():
            jax.block_until_ready(arr)
        out = np.asarray(arr)
        if entry.batch_index is not None:
            out = out[entry.batch_index]
        return out

    def _refine(self, entry: StoredResponse, levels: int, up_to_level: int,
                heal: bool, partial: bool) -> np.ndarray:
        dec = progressive.decode_progressive(
            entry.source, up_to_level, heal=heal, partial=partial
        )
        arr = self._row(progressive.reconstruct(dec, backend=self.backend), entry)
        crop = tier_shape(entry.image_shape, levels, up_to_level)
        return arr[tuple(slice(0, s) for s in crop)]

    # -- tiers ---------------------------------------------------------------

    def thumbnail(self, uid: int, *, heal: bool = True) -> np.ndarray:
        """The approximation band for ``uid`` — header + ONE band read."""
        with obs.span("serve.read", subsystem="serve", uid=uid, tier=0):
            entry = self._entry(uid)
            dec = progressive.decode_lowband(entry.source, heal=heal)
            thumb = self._row(dec.band, entry)
            crop = tier_shape(entry.image_shape, dec.levels, 0)
            return thumb[tuple(slice(0, s) for s in crop)]

    def refine(
        self,
        uid: int,
        up_to_level: int,
        *,
        heal: bool = True,
        partial: bool = False,
    ) -> np.ndarray:
        """``uid`` reconstructed from its coarsest ``up_to_level`` levels."""
        with obs.span("serve.read", subsystem="serve", uid=uid, tier=up_to_level):
            entry = self._entry(uid)
            h = progressive.read_header(entry.source)
            return self._refine(entry, h.levels, up_to_level, heal, partial)

    def full(self, uid: int, *, heal: bool = True) -> np.ndarray:
        """The original samples, bit-exact (every byte range read)."""
        with obs.span("serve.read", subsystem="serve", uid=uid) as attrs:
            entry = self._entry(uid)
            h = progressive.read_header(entry.source)
            attrs["tier"] = h.levels
            return self._refine(entry, h.levels, h.levels, heal, partial=False)

    def tiers(self, uid: int) -> Dict[int, Shape]:
        """Available fidelity tiers: ``{up_to_level: shape}`` for ``uid``."""
        entry = self._entry(uid)
        h = progressive.read_header(entry.source)
        return {
            lv: tier_shape(entry.image_shape, h.levels, lv)
            for lv in range(h.levels + 1)
        }
