"""walk: the default reference plan, Algorithm 1 of GateANN from the
medoid at the configuration's ``search.mode`` (``bench/reference/oracle.py``).

A plan file gives, for the configurations whose ``reference`` names it
(or, for this one, that name none):

  * ``SECTIONS``: the index-file sections it reads
    (``bench/reference/index_file.py``; the medoid comes with them);
  * ``answers(sections, corpus, queries, masks, config, precision)``:
    the top-K ids of each query, where ``masks[i]`` is the (N,) bool mask
    of the records that pass query ``i``'s predicate (the filter schema's
    ``passes``), ``config`` the configuration and ``precision`` ``"f32"``
    or ``"bf16"`` (the control).

A plan uses NumPy and the benchmark's reference alone, and imports
nothing of the program.
"""
import numpy as np

from bench.reference import oracle

SECTIONS = ("neighbors", "pq_books", "pq_codes")


def answers(sections: dict, corpus: np.ndarray, queries: np.ndarray, masks,
            config: dict, precision: str) -> list:
    index = oracle.Index(
        vectors=corpus,
        books=np.asarray(sections["pq_books"], np.float32),
        codes=np.asarray(sections["pq_codes"], np.int64),
        neighbors=np.asarray(sections["neighbors"], np.int64),
        entry=sections["medoid"], r_max=int(config["index"]["r_max"]))
    s = config["search"]
    kw = dict(mode=s["mode"], L=int(s["search_l"]), W=int(s["beam_width"]),
              K=int(s["result_k"]), max_hops=int(s["max_hops"]), precision=precision)
    return [oracle.search(index, q, m, **kw)[0] for q, m in zip(queries, masks)]
