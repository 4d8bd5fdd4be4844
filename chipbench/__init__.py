"""chipbench — the benchmark of torchsnapshot_tpu on the chip (see PERF.md)."""
