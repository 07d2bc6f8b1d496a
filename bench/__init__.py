"""The benchmark of the DSE: cells, their harness and their references."""
