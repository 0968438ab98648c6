"""Seconds a heal took to hand what it regenerated to the planned
holders (stage `distribute` of the `ec.peer_rebuild` span: copy to the
holder, mount there, delete here), per heal of the window."""

from ecbench.harness import load_module

H = load_module("layers", "heal_mb_per_s")


def read(obs, cell):
    spans = [d for d in H.heals(obs) if "distribute" in d["stages"]]
    if not spans:
        return None
    return sum(d["stages"]["distribute"]["seconds"] for d in spans) / len(spans)
