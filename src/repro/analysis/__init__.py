"""Analysis tools: the foreaction-graph miner that folds recorded syscall
traces into speculatable graphs, and its online re-miner."""

from .mine import (MinedGraph, ReplayMismatch, UnminableTrace, UnsoundGraph,
                   mine_and_validate, mine_traces, preissue_overlap,
                   replay_trace, synthesize_trace)
from .remine import ReMineConfig, ReMiner

__all__ = [
    "MinedGraph", "ReplayMismatch", "UnminableTrace", "UnsoundGraph",
    "mine_and_validate", "mine_traces", "replay_trace",
    "preissue_overlap", "synthesize_trace", "ReMineConfig", "ReMiner",
]
