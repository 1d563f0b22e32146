"""What the language-model drivers share: the model as the program builds it
from a configuration file, the seeded documents, the weights, and the
program's trainer built the way ``launch/train.py`` builds it."""
from __future__ import annotations

import dataclasses
import threading

import numpy as np

from chipbench import harness

__all__ = ["SeededDocs", "SelectionTap", "program_config", "reference_module",
           "build_trainer", "lm_shape"]


class SeededDocs:
    """``n_docs`` token sequences made from the seed in one bulk draw.

    Each document has a topic; its tokens are Zipf-distributed ranks mapped
    through the topic's own permutation of the vocabulary, so documents of
    one topic share their frequent tokens and CRAIG's proxies cluster.
    ``batch(idx)`` is what the program's ``Trainer`` and ``ProxyExtractor``
    read; every call's indices are recorded, in order.
    """

    def __init__(self, seed: int, n_docs: int, seq_len: int, vocab: int,
                 n_topics: int, zipf_a: float):
        rng = np.random.default_rng([int(seed), 0xD0C5])
        perms = np.stack([rng.permutation(vocab).astype(np.int32)
                          for _ in range(n_topics)])
        ranks = rng.zipf(zipf_a, size=(n_docs, seq_len + 1)) % vocab
        topic = np.arange(n_docs) % n_topics
        toks = perms[topic[:, None], ranks]
        self.tokens = np.ascontiguousarray(toks[:, :-1])
        self.labels = np.ascontiguousarray(toks[:, 1:])
        self.calls: list[np.ndarray] = []
        self._lock = threading.Lock()

    @property
    def n_docs(self) -> int:
        return self.tokens.shape[0]

    def batch(self, idx) -> dict:
        idx = np.asarray(idx, np.int64)
        with self._lock:
            self.calls.append(idx.copy())
        return {"tokens": self.tokens[idx], "labels": self.labels[idx]}


class SelectionTap:
    """The program's CRAIG selection as the configuration runs it, watched.

    The ``Trainer`` builds a ``CraigSelector`` for every refresh.  While the
    tap is open, that selector runs inside ``jax.default_matmul_precision``
    at the configuration's ``selection.matmul_precision`` (on a TPU a
    float32 matmul at the default precision rounds its operands to
    bfloat16; the configuration states float32 selection), and the last two
    calls are kept as ``(features, warm-start prefix, selection)``.
    """

    def __init__(self, cell: harness.Cell):
        import jax
        import repro.train.trainer as trainer_mod

        precision = cell.config["selection"]["matmul_precision"]
        self._module, self._base = trainer_mod, trainer_mod.CraigSelector
        self.calls: list[tuple] = []
        tap = self

        class Selector(self._base):
            def select(self, feats, labels=None, init_selected=None):
                with jax.default_matmul_precision(precision):
                    sel = super().select(feats, labels=labels,
                                         init_selected=init_selected)
                tap.calls = tap.calls[-1:] + [(feats, init_selected, sel)]
                return sel

        trainer_mod.CraigSelector = Selector

    def close(self) -> None:
        self._module.CraigSelector = self._base


def program_config(cell: harness.Cell):
    """The program's ``ModelConfig``: its registry entry with every size of
    the configuration file (test overrides first)."""
    from repro.configs.registry import get_config

    hf = dict(cell.config["hf_config"])
    hf.update(cell.overrides.get("hf_config", {}))
    base = get_config(cell.config["program_config"])
    return dataclasses.replace(
        base,
        n_layers=int(hf["num_hidden_layers"]),
        d_model=int(hf["hidden_size"]),
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        d_head=int(hf["head_dim"]),
        d_ff=int(hf["intermediate_size"]),
        vocab_size=int(hf["vocab_size"]),
        rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        qk_norm=True,
        qkv_bias=bool(hf.get("attention_bias", False)),
    ), hf


def reference_module(cell: harness.Cell):
    return harness.load_module(
        harness.BENCH_DIR / "reference" / f"{cell.config['reference']}.py")


def lm_shape(hf: dict, seq_len: int):
    from chipbench.flops import LMShape

    return LMShape.from_hf(hf, seq_len)


def build_trainer(cell: harness.Cell, seed: int, cfg, weights_fn, docs,
                  refresh_mode: str):
    """The program's ``Trainer`` with a ``device``-engine CRAIG refresh, its
    tiles as the configuration's ``selection`` states them."""
    from repro.core.craig import CraigConfig
    from repro.core.engines import DeviceConfig
    from repro.optim import adamw, constant
    from repro.train import Trainer, TrainerConfig

    batch = int(cell.param("batch"))
    opt = cell.param("optimizer")
    sel = cell.config["selection"]
    if sel["engine"] != "device":
        raise ValueError(f"unsupported selection engine {sel['engine']!r}")
    tcfg = TrainerConfig(
        batch_size=batch,
        select_every_epochs=int(cell.param("select_every_epochs")),
        craig=CraigConfig(fraction=float(cell.param("craig_fraction")),
                          per_class=False, engine=DeviceConfig(
                              tile_dtype=sel["tile_dtype"],
                              block_n=int(sel["block_n"]))),
        proxy_pool_batches=docs.n_docs // batch,
        refresh_mode=refresh_mode,
        warm_start_fraction=float(cell.param("warm_start_fraction")),
        seed=int(seed),
    )
    optimizer = adamw(constant(float(opt["lr"])), b1=float(opt["b1"]),
                      b2=float(opt["b2"]), eps=float(opt["eps"]),
                      weight_decay=0.0, clip=float(opt["clip"]))
    return Trainer(cfg, tcfg, docs, optimizer, weights_fn)
