"""Per cent of the window's GETs that ran a reconstruction: those whose
`http.volume` root holds an `ec.degraded_read` span with a `reconstruct`
stage. A GET that hit the interval cache or waited on another's build
holds the span without the stage; a healthy GET holds no such span."""

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    roots = get_roots(obs)
    if not roots:
        return None
    ran = sum(
        any(d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"] for d in walk(r))
        for r in roots
    )
    return 100.0 * ran / len(roots)
