"""A decoder LM's configuration file, read for the harness.

Everything that knows the KEYS of an `lm` configuration (`n_embd`, `n_layer`,
`vocab_size`, ...) or the shape of its data is here, found by the file's
`family`; the drivers know neither. A configuration of another family brings
a file of its own beside this one.
"""

from __future__ import annotations

import os

import numpy as np

from perf.lib import flops


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


# ---------------------------------------------------------------- training
def write_data(cfg: dict, traffic: dict, rng, batch: int, steps: int,
               data_dir: str) -> dict:
    """A byte stream for the Trainer's "text" corpus (its vocabulary is 256),
    of exactly `steps` batches of windows after the Trainer's own hold-out.
    Returns the registry name and the benchmark's copy of what it wrote."""
    if cfg["vocab_size"] != 256:
        raise ValueError("the Trainer takes its vocabulary from its corpus: "
                         "a byte stream trains a vocabulary of 256, not "
                         f"{cfg['vocab_size']}")
    window = traffic["seq_len"] + 1
    # the Trainer holds out max(10%, one batch) for its evaluation split
    n_train = batch * steps * window
    total = n_train + max(batch * window, n_train // 9 + window)
    stream = rng.integers(0, 256, total, dtype=np.uint8)
    os.makedirs(data_dir, exist_ok=True)
    for name in os.listdir(data_dir):  # the registry reads every file here
        os.remove(os.path.join(data_dir, name))
    stream.tofile(os.path.join(data_dir, "stream.bin"))
    return {"dataset": "text", "arrays": stream}


def rows_fed(data: dict, traffic: dict, idx) -> dict:
    """The batch of window starts `idx`, from the benchmark's own stream."""
    window = traffic["seq_len"] + 1
    stream = data["arrays"]
    return {"tokens": np.stack(
        [stream[s:s + window] for s in idx]).astype(np.int32)}


def trainer_options(cfg: dict, traffic: dict) -> dict:
    """Fields of the program's TrainConfig that follow from the files."""
    return {"seq_len": traffic["seq_len"],
            "pos_emb": cfg["position_embedding"],
            "tied_embeddings": cfg["tie_word_embeddings"]}


def train_flops_per_item(cfg: dict, traffic: dict) -> float:
    """fwd + bwd operations per sequence of `seq_len` tokens."""
    seq = traffic["seq_len"]
    return 3.0 * seq * flops.lm_forward_flops_per_token(cfg, seq)


# ----------------------------------------------------------------- serving
def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving."""
    return {"vocab_size": cfg["vocab_size"], "max_len": cfg["n_positions"],
            "pos_emb": cfg["position_embedding"],
            "tied_embeddings": cfg["tie_word_embeddings"]}


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    all layers, in the served type (bf16)."""
    q_and_out = 2 * cfg["n_layer"] * cfg["n_embd"] * 2
    return flops.kv_bytes_per_token(cfg), q_and_out
