"""The repo's benchmark: cells, metrics and the yardstick they are
measured with. See perfbench/README.md."""
