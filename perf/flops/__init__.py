"""Operations and bytes from shapes: one module per architecture, named by
a configuration's `flops` key (see `perf/run.py`)."""
