"""The paper's own experimental configurations (Section 5.1).

Datasets are synthetic stand-ins with matched class counts (see
repro.data.synthetic: real CIFAR, Tiny-ImageNet and AG-News cannot be
fetched offline).  Sizes are scaled by ``scale`` so CPU runs finish; the Dirichlet
non-IID machinery, client counts, participation ratios, local epochs and
hyper-parameters mirror the paper exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class PaperTask:
    name: str
    kind: str                  # "image" | "text"
    model: str                 # "resnet8" | "resnet50" | "mlp" | "distilbert"
    num_classes: int
    train_size: int            # paper's training-set size
    n_clients: int
    rounds: int
    local_epochs: int
    participation: float       # C
    batch_size: int = 64
    lr: float = 0.05
    momentum: float = 0.9
    weight_decay: float = 1e-5
    optimizer: str = "sgd"
    gamma: float = 0.2         # FedGKD distillation coefficient
    buffer_m: int = 5          # FedGKD(-VOTE) buffer
    image_hw: int = 32
    # text tasks
    seq_len: int = 64
    vocab_size: int = 2000
    d_model: int = 128
    # tabular (mlp) tasks
    feat_dim: int = 16


CIFAR10 = PaperTask("cifar10", "image", "resnet8", num_classes=10,
                    train_size=45_000, n_clients=20, rounds=100,
                    local_epochs=20, participation=0.2, gamma=0.2)
CIFAR100 = PaperTask("cifar100", "image", "resnet8", num_classes=100,
                     train_size=45_000, n_clients=20, rounds=100,
                     local_epochs=20, participation=0.2, gamma=0.2)
TINY_IMAGENET = PaperTask("tiny-imagenet", "image", "resnet50", num_classes=200,
                          train_size=90_000, n_clients=20, rounds=30,
                          local_epochs=20, participation=0.2, gamma=0.1,
                          image_hw=64)
AG_NEWS = PaperTask("ag-news", "text", "distilbert", num_classes=4,
                    train_size=60_000, n_clients=20, rounds=10,
                    local_epochs=1, participation=0.2, optimizer="adam",
                    lr=1e-5, weight_decay=0.0, gamma=0.2, buffer_m=3)
SST5 = PaperTask("sst5", "text", "distilbert", num_classes=5,
                 train_size=4_272, n_clients=10, rounds=10,
                 local_epochs=3, participation=0.4, optimizer="adam",
                 lr=1e-5, weight_decay=0.0, gamma=0.2, buffer_m=3)
# not from the paper: a light MLP workload for executor benchmarks/examples
TOY = PaperTask("toy", "tabular", "mlp", num_classes=10,
                train_size=2_000, n_clients=16, rounds=20,
                local_epochs=2, participation=0.5, batch_size=32,
                lr=0.05, weight_decay=0.0, feat_dim=16)

PAPER_TASKS = {t.name: t for t in (CIFAR10, CIFAR100, TINY_IMAGENET, AG_NEWS,
                                   SST5, TOY)}


def scaled(task: PaperTask, scale: float, rounds: Optional[int] = None,
           local_epochs: Optional[int] = None) -> PaperTask:
    """Shrink dataset size / rounds for CPU execution; everything else kept."""
    return dataclasses.replace(
        task,
        train_size=max(task.n_clients * 2 * task.num_classes,
                       int(task.train_size * scale)),
        rounds=rounds if rounds is not None else task.rounds,
        local_epochs=local_epochs if local_epochs is not None else task.local_epochs)


def distilbert_class_config(task: PaperTask) -> ModelConfig:
    """DistilBERT-class text encoder (6L, LN+GELU) used as a classifier
    backbone for the paper's NLP tasks (scaled width for CPU)."""
    return ModelConfig(
        name=f"distilbert-{task.name}", family="dense",
        n_layers=4, d_model=task.d_model, n_heads=4, n_kv_heads=4,
        d_ff=4 * task.d_model, vocab_size=task.vocab_size, head_dim=0,
        norm="ln", act="gelu", tie_embeddings=True,
        param_dtype="float32", activation_dtype="float32",
        scan_layers=True)
