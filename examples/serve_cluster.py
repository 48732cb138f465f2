"""End-to-end serving driver (the paper's kind of system): batched requests
routed by the NSGA-II policy across real JAX model instances standing in for
the cloud/edge testbed, with continuous batching, a mid-run node failure,
and straggler hedging.

    PYTHONPATH=src python examples/serve_cluster.py
"""
from repro.launch.serve import main

if __name__ == "__main__":
    # the launcher is the real entry point; this example drives it with a
    # failure injection so the fault-tolerance path is exercised visibly
    main(["--requests", "24", "--optimize-router",
          "--fail-node", "1", "--fail-at", "8"])
