"""Graph generators, one file each, found by the name a configuration's
``graph.generator`` gives (see ``registry.Benchmark.generator``)."""
