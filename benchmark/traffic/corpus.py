"""Training traffic: a Megatron mmap corpus written from the seed.

One general writer; a mix is its parameter file (``traffic/<name>.json``,
``"kind": "corpus"``).  Documents are arithmetic progressions inside one band
of token ids, so that a few updates can lower the loss from ln(vocab); their
lengths are uniform in ``doc_tokens``.  The corpus goes through the program's
own ``MemmapTokenWriter`` because that file format is the trainer's input.
"""

from __future__ import annotations

import os

import numpy as np


def documents(traffic: dict, vocab: int, seed: int):
    """Yields the corpus's documents (lists of token ids), from the seed."""
    rs = np.random.RandomState(seed % (2**32))
    band_width = traffic["band_width"]
    band = int(rs.randint(vocab - band_width))
    lo, hi = traffic["doc_tokens"]
    n_tokens = 0
    while n_tokens < traffic["corpus_tokens"]:
        n = int(rs.randint(lo, hi))
        start, stride = int(rs.randint(band_width)), int(rs.choice(traffic["strides"]))
        yield ((start + stride * np.arange(n)) % band_width + band).tolist()
        n_tokens += n


def write_corpus(traffic: dict, vocab: int, seed: int, run_dir: str) -> str:
    """Writes the corpus and its Megatron YAML under ``run_dir``; returns the
    YAML's path."""
    from relora_tpu.data.memmap import MemmapTokenWriter, best_dtype

    os.makedirs(run_dir, exist_ok=True)
    prefix = os.path.join(run_dir, "corpus")
    with MemmapTokenWriter(prefix, dtype=best_dtype(vocab)) as w:
        for doc in documents(traffic, vocab, seed):
            w.add_document(doc)
    cfg = os.path.join(run_dir, "mega.yaml")
    with open(cfg, "w") as f:
        f.write(
            f'data_path: {prefix}\nsplit: "{traffic["split"]}"\nseq_length: {traffic["seq_length"]}\n'
            f"seed: {seed % (2**31 - 1)}\ndata_impl: mmap\n"
        )
    return cfg
