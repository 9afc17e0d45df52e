"""Data generators owned by the benchmark."""
from __future__ import annotations

# what `graph500` builds, as a configuration's `graph` entry states it
GRAPH500_BUILDS = dict(symmetric=True, self_loops=False,
                       duplicate_edges=False, isolated_vertices="dropped")


def load_graph(spec: dict):
    """(row_ptr, col_idx, out_deg) numpy arrays of the graph a
    configuration's `graph` entry describes. The entry states the graph's
    properties; one the generator does not build is refused."""
    if spec["generator"] != "graph500":
        raise ValueError(f"unknown graph generator {spec['generator']!r}")
    for key, value in GRAPH500_BUILDS.items():
        if spec.get(key) != value:
            raise ValueError(f"graph500 builds {key}={value!r}, the "
                             f"configuration states {spec.get(key)!r}")
    from bench.data.graph500 import graph500
    return graph500(int(spec["scale"]), int(spec["seed"]),
                    edgefactor=int(spec["edgefactor"]),
                    initiator=tuple(spec["initiator"]))


def csr_graph(spec: dict):
    """The program's `CSRGraph` of that graph, its arrays left in host
    memory as a loaded dataset's are: the engines copy what they need to
    the device themselves."""
    from repro.core.graph import CSRGraph
    row_ptr, col_idx, out_deg = load_graph(spec)
    return CSRGraph(row_ptr=row_ptr, col_idx=col_idx, out_deg=out_deg,
                    n=len(out_deg), m=len(col_idx), undirected=True)
