"""A saved index file, read with NumPy alone.

The reference reads the file by its layout, not through the program's
loader: a 16-byte prelude (magic ``GANN``, format version as a
little-endian uint32, length of a JSON table as a uint64), the JSON
table, and each section at the byte offset the table gives, with its
dtype and shape.  Only the sections a reference plan names are read
(``SECTIONS`` of ``bench/reference/plans/<plan>.py``), and the medoid.
"""
from __future__ import annotations

import json

import numpy as np

MAGIC = b"GANN"
PRELUDE = np.dtype([("magic", "S4"), ("version", "<u4"), ("json_len", "<u8")])


def read(path: str, sections) -> dict:
    """``{name: array for name in sections, "medoid": int}``; a section
    the file does not hold is refused by name."""
    with open(path, "rb") as f:
        prelude = np.frombuffer(f.read(PRELUDE.itemsize), PRELUDE)[0]
        if bytes(prelude["magic"]) != MAGIC:
            raise ValueError(f"{path}: not an index file")
        table = json.loads(f.read(int(prelude["json_len"])))
        out = {"medoid": int(table["medoid"])}
        for name in sections:
            s = table["sections"].get(name)
            if s is None:
                raise ValueError(f"{path}: no section {name!r}; it holds "
                                 f"{sorted(table['sections'])}")
            f.seek(int(s["offset"]))
            count = int(np.prod(s["shape"]))
            arr = np.fromfile(f, dtype=np.dtype(s["dtype"]), count=count)
            if arr.size != count:
                raise ValueError(f"{path}: section {name} is truncated")
            out[name] = arr.reshape(s["shape"])
    return out
