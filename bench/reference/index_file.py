"""A saved index file, read with NumPy alone.

The reference reads the file by its layout, not through the program's
loader: a 16-byte prelude (magic ``GANN``, format version as a
little-endian uint32, length of a JSON table as a uint64), the JSON
table, and each section at the byte offset the table gives, with its
dtype and shape.  Only the sections a search runs over are read: the
full adjacency rows, the PQ codebooks and codes, and the medoid.
"""
from __future__ import annotations

import json

import numpy as np

MAGIC = b"GANN"
PRELUDE = np.dtype([("magic", "S4"), ("version", "<u4"), ("json_len", "<u8")])
SECTIONS = ("neighbors", "pq_books", "pq_codes")


def read(path: str) -> dict:
    """``{"neighbors", "pq_books", "pq_codes": array, "medoid": int}``."""
    with open(path, "rb") as f:
        prelude = np.frombuffer(f.read(PRELUDE.itemsize), PRELUDE)[0]
        if bytes(prelude["magic"]) != MAGIC:
            raise ValueError(f"{path}: not an index file")
        table = json.loads(f.read(int(prelude["json_len"])))
        out = {"medoid": int(table["medoid"])}
        for name in SECTIONS:
            s = table["sections"][name]
            f.seek(int(s["offset"]))
            count = int(np.prod(s["shape"]))
            arr = np.fromfile(f, dtype=np.dtype(s["dtype"]), count=count)
            if arr.size != count:
                raise ValueError(f"{path}: section {name} is truncated")
            out[name] = arr.reshape(s["shape"])
    return out
