"""The benchmark's own traffic: the token stream and the simulated cluster
a mix describes.

The stream is ``streams/<kind>.py`` for the mix's ``stream.kind``; the
cluster is built from the node coefficients and comm model the mix file
lists, so a change to the program's catalog cannot move the yardstick.
"""
from __future__ import annotations

from typing import Any, Dict

from chipbench import spec


def make_stream(mix: Dict[str, Any], vocab: int, seed: int):
    """The mix's token stream: ``batch(step, rows)`` of seed ``seed``."""
    params = mix["stream"]
    return spec.module("streams", params["kind"]).make(params, vocab, mix["seq_len"], seed)


def make_cluster(mix: Dict[str, Any], seed: int):
    """The program's ``SimulatedCluster`` over the mix's node models."""
    from repro.core.perf_model import CommModel
    from repro.core.simulator import NodeProfile, SimulatedCluster

    profiles = [
        NodeProfile(name=n["name"], q=n["q"], s=n["s"], k=n["k"], m=n["m"])
        for n in mix["nodes"]
    ]
    c = mix["comm"]
    comm = CommModel(t_o=c["t_o"], t_u=c["t_u"], gamma=c["gamma"])
    return SimulatedCluster(profiles, comm, noise=mix["timing_noise"], seed=seed)
