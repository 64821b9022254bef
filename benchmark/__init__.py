"""The benchmark of keystone_tpu: cells, metrics and the yardstick.

Everything a cell is measured with lives in this package, where later
PRs add files and change none: see README.md. The command is
``python -m benchmark.run``, run from the root of the checkout."""
