"""The two arithmetics of a block's f-term, for parametrised parity tests.

``"tile"``: the store carries its dense masked tile (``sparse.with_tile``
attaches it where the backend's rule would not build one at the test's
size), so the f-term comes from three matrix products.  ``"segment"``: the
tile is dropped, so it comes from the segment-sorted entries, as on a store
whose blocks are too sparse for a tile.
"""

from repro import sparse

PATHS = ("tile", "segment")


def on_path(sp, path):
    """``sp`` made to take ``path`` ("tile" or "segment")."""

    if path == "tile":
        return sparse.with_tile(sp)
    if path == "segment":
        return sparse.drop_tile(sp)
    raise ValueError(f"unknown path {path!r}")
