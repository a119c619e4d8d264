"""SLO benchmark of the paged serving engine on TPU (see PERF.md)."""
