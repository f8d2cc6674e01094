"""The benchmark: harness, yardstick and data. ``PERF.md`` says what it
measures and why; ``BENCHMARK.json`` lists the cells. Nothing in the
package imports this directory, and later PRs add files here without
editing the ones that exist."""
