"""A ViT classifier's configuration file, read for the harness (see
`families/lm.py`)."""

from __future__ import annotations

import json
import os

import numpy as np

from perf.lib import flops


def prepare(cfg: dict) -> None:
    """ViT-B/16's patch of 16 has no option in the Trainer (the registry's
    `vit_base` keeps the class's default of 4). The file names a
    `program_model_base` and the keyword arguments to add, and this
    registers that variant of the program's own model under
    `program_model`, through `models.register`, the program's own extension
    point, so that `Trainer(config)` finds it by name as it finds any model
    a user registers. A `--patch_size` option in the program would let a
    later PR delete this (PERF.md, Open questions)."""
    base, name = cfg.get("program_model_base"), cfg["program_model"]
    if not base:
        return
    from ddp_practice_tpu import models

    extra = dict(cfg["program_model_kwargs"])
    try:
        models.create_model(name)
        return  # registered already (a second build in one process)
    except ValueError:
        pass  # "unknown model": register it

    @models.register(name, fused_capable=models.accepts_fused(base))
    def _variant(**kw):
        return models.create_model(base, **{**kw, **extra})


def write_data(cfg: dict, traffic: dict, rng, batch: int, steps: int,
               data_dir: str) -> dict:
    """uint8 images and labels as the Trainer's "imagenet" registry loads
    them; rows all differ."""
    root = os.path.join(data_dir, "imagenet-arrays")
    os.makedirs(root, exist_ok=True)
    shape = (cfg["image_size"], cfg["image_size"], cfg["num_channels"])
    arrays = {}
    for split, n in (("train", batch * steps), ("test", batch)):
        images = rng.integers(0, 256, (n,) + shape, dtype=np.uint8)
        labels = rng.integers(0, cfg["num_labels"], n).astype(np.int32)
        np.save(os.path.join(root, f"{split}-images.npy"), images)
        np.save(os.path.join(root, f"{split}-labels.npy"), labels)
        arrays[split] = (images, labels)
    with open(os.path.join(root, "meta.json"), "w") as f:
        json.dump({"num_classes": cfg["num_labels"], "name": "perf-seeded",
                   "splits": {"train": {}, "test": {}}}, f)
    return {"dataset": "imagenet", "arrays": arrays["train"]}


def rows_fed(data: dict, traffic: dict, idx) -> dict:
    images, labels = data["arrays"]
    return {"image": images[idx], "label": labels[idx]}


def trainer_options(cfg: dict, traffic: dict) -> dict:
    return {}


def train_flops_per_item(cfg: dict, traffic: dict) -> float:
    """fwd + bwd operations per image."""
    return 3.0 * flops.vit_forward_flops_per_image(cfg)
