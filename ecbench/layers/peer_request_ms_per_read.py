"""Milliseconds in `request_rtt` per read from a peer: from before the
reader's `sendall` of the request to the response header parsed (over
the `VolumeEcShardRead` fallback: to the first chunk). The request's
way to the holder, the holder's two `recv`s, its look-up of the shard
and its header's way back lie in it, and every hand-over of the two
interpreters between them.

"Per read", here and in the six readers beside this one, is the mean
over the window's `ec.peer_read` spans that were answered in full
(`answered` 1) and not closed under their thread by the reconstruction
that had started them (`unused`). A read and its holder's side are
joined by id: the holder's `rpc.ec_shard_read` root names the read's
span as its parent. This file holds what the seven share; a program
that opens no such span gives each of them nothing to read."""

from ecbench.layerlib import walk

READ_OP = "ec.peer_read"
SERVE_OP = "rpc.ec_shard_read"


def reads(obs, kind=None) -> list[dict]:
    """The window's answered reads from peers, wherever in a root's tree
    they lie; of `kind` (`interval` | `sibling`) only where one is given."""
    return [
        d for root in obs.spans for d in walk(root)
        if d["op"] == READ_OP and d["attrs"].get("answered") == 1
        and not d["attrs"].get("unused") and kind in (None, d["attrs"].get("kind"))
    ]


def stage_ms_per_read(obs, stage: str, kind=None) -> float | None:
    got = reads(obs, kind)
    if not got:
        return None
    return 1e3 * sum(d["stages"].get(stage, {}).get("seconds", 0.0) for d in got) / len(got)


def joined(obs) -> list[tuple[dict, list[dict]]]:
    """(read, the holders' root spans that name it as their parent), for
    every answered read that has one: one as a rule, more where the
    reader asked again (another peer, or the same one's stream)."""
    served: dict[str, list[dict]] = {}
    for d in obs.spans:
        if d["op"] == SERVE_OP and d.get("parent_span_id"):
            served.setdefault(d["parent_span_id"], []).append(d)
    return [(r, served[r["span_id"]]) for r in reads(obs) if r.get("span_id") in served]


def read(obs, cell):
    return stage_ms_per_read(obs, "request_rtt")
