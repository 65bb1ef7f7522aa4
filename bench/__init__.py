"""On-chip benchmark of the threaded ShadowSync DLRM trainer (see PERF.md)."""
