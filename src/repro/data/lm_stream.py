"""Synthetic token streams: the LM stream of the ~100M example, and the
token-tagging devices of the federated Zamba2 client.

A seeded order-1 Markov chain over a Zipf-distributed vocabulary with
sticky "topic" states: non-trivial (learnable) structure so loss curves
actually move, fully procedural so no dataset download is needed.

``build_token_network`` gives each device sequences from one of
``num_domains`` domains.  Every domain has its own topic -> token table
over the whole vocabulary; a share ``shared`` of each topic's tokens is
common to all domains, so the tables partly overlap.  The topics (the
classes) are shared across domains, a sequence stays on its topic with
probability ``stay`` per token, and each token's label is the topic that
generated it (token tagging, as in FedNLP's sequence-tagging tasks,
arXiv:2104.08815).  Labels are shown per sequence by the paper's
protocol: half the devices partially labeled (ratio 0.3-0.9, at least
one sequence), the rest unlabeled.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Tuple

import numpy as np

from repro.data.partition import DeviceData, assign_label_ratios


@dataclasses.dataclass
class LMStreamConfig:
    vocab_size: int = 32768
    num_topics: int = 32
    topic_vocab: int = 2048        # tokens reachable from each topic
    topic_stay_prob: float = 0.98
    zipf_a: float = 1.2
    seed: int = 0


class LMStream:
    """Stateless batch sampler: (tokens, labels) int32 arrays."""

    def __init__(self, cfg: LMStreamConfig):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        # per-topic vocabulary subsets + zipf weights over them
        self.topic_tokens = np.stack([
            rng.choice(cfg.vocab_size, size=cfg.topic_vocab, replace=False)
            for _ in range(cfg.num_topics)])
        ranks = np.arange(1, cfg.topic_vocab + 1, dtype=np.float64)
        w = ranks ** (-cfg.zipf_a)
        self.token_probs = w / w.sum()

    def sample(self, batch: int, seq_len: int, seed: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        cfg = self.cfg
        rng = np.random.default_rng(seed)
        topics = rng.integers(0, cfg.num_topics, size=batch)
        toks = np.empty((batch, seq_len + 1), np.int64)
        for t in range(seq_len + 1):
            switch = rng.random(batch) > cfg.topic_stay_prob
            topics = np.where(switch,
                              rng.integers(0, cfg.num_topics, size=batch),
                              topics)
            pick = rng.choice(cfg.topic_vocab, size=batch, p=self.token_probs)
            toks[:, t] = self.topic_tokens[topics, pick]
        return (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32))

    def batches(self, batch: int, seq_len: int, start_seed: int = 1
                ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        s = start_seed
        while True:
            yield self.sample(batch, seq_len, s)
            s += 1


def domain_topic_tables(vocab_size: int, num_topics: int, num_domains: int,
                        topic_vocab: int, shared: float,
                        rng: np.random.Generator) -> np.ndarray:
    """(num_domains, num_topics, topic_vocab) token ids: per topic, the
    first ``round(shared * topic_vocab)`` are common to every domain, the
    rest each domain's own draw."""
    n_sh = int(round(shared * topic_vocab))
    out = np.empty((num_domains, num_topics, topic_vocab), np.int32)
    for t in range(num_topics):
        out[:, t, :n_sh] = rng.choice(vocab_size, n_sh, replace=False)
        for d in range(num_domains):
            out[d, t, n_sh:] = rng.choice(vocab_size, topic_vocab - n_sh,
                                          replace=False)
    return out


def sample_topic_tokens(table: np.ndarray, probs: np.ndarray, n: int,
                        seq_len: int, stay: float,
                        rng: np.random.Generator):
    """(tokens, topics), each (n, seq_len) int32, from one domain's
    (num_topics, topic_vocab) table: sticky topics, Zipf picks."""
    num_topics, topic_vocab = table.shape
    switch = rng.random((n, seq_len)) > stay
    fresh = rng.integers(0, num_topics, (n, seq_len))
    pick = rng.choice(topic_vocab, size=(n, seq_len), p=probs)
    topics = np.empty((n, seq_len), np.int32)
    topics[:, 0] = fresh[:, 0]
    for t in range(1, seq_len):
        topics[:, t] = np.where(switch[:, t], fresh[:, t], topics[:, t - 1])
    return table[topics, pick].astype(np.int32), topics


def build_token_network(num_devices: int, samples_per_device: int,
                        seq_len: int, *, vocab_size: int, seed: int,
                        num_topics: int = 10, num_domains: int = 3,
                        topic_vocab: int = 2048, shared: float = 0.5,
                        stay: float = 0.98,
                        zipf_a: float = 1.2) -> List[DeviceData]:
    """Devices of ``samples_per_device`` sequences each; device i draws
    from domain i mod ``num_domains`` (see the module docstring).  A
    topic reaches at most half the vocabulary."""
    topic_vocab = min(topic_vocab, vocab_size // 2)
    rng = np.random.default_rng([seed, 31])
    tables = domain_topic_tables(vocab_size, num_topics, num_domains,
                                 topic_vocab, shared, rng)
    w = np.arange(1, topic_vocab + 1, dtype=np.float64) ** (-zipf_a)
    probs = w / w.sum()
    ratios = assign_label_ratios(num_devices, rng)
    devices = []
    for i, ratio in enumerate(ratios):
        dom = i % num_domains
        toks, topics = sample_topic_tokens(tables[dom], probs,
                                           samples_per_device, seq_len,
                                           stay, rng)
        n = samples_per_device
        k = int(np.clip(round(ratio * n), 1, n)) if ratio > 0 else 0
        mask = np.zeros(n, bool)
        mask[rng.permutation(n)[:k]] = True
        devices.append(DeviceData(
            toks, np.where(mask[:, None], topics, -1).astype(np.int32),
            mask, np.full(n, dom, np.int32), topics))
    return devices
