"""A seed's first run in a checkout: load the configuration's corpus,
flush it, close the node. A process of its own, which holds the chip
while it runs and ends before the run's own process touches JAX.

    python benchmarks/harness/loader.py <config file> <seed> <docs> <rehearse 0|1>

Exits 2, having loaded nothing, where it is not a rehearsal and JAX
finds no TPU.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv: list[str]) -> int:
    path, seed, docs, rehearse = argv[1], int(argv[2]), int(argv[3]), \
        int(argv[4])
    from harness import die_with_parent
    die_with_parent()
    import jax
    from elasticsearch_tpu.utils.compile_cache import configure_compile_cache

    from harness import corpus as C
    from harness import served as S

    platform = jax.devices()[0].platform
    if not rehearse and platform != "tpu":
        print(f"loader.py: no TPU, JAX found [{platform}]", file=sys.stderr)
        return 2
    configure_compile_cache()
    with open(path) as f:
        config = json.load(f)
    corpus = C.corpus_of(config, docs, seed)
    tag = f"[{platform} loader]"
    with S.Served(config, seed, docs,
                  log=lambda *parts: print(tag, *parts, flush=True)) as sv:
        sv.store(corpus)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
