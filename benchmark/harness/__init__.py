"""The benchmark's yardstick: loading of cells, the window drivers, the
reduction from trace to numbers, the peaks and the comparison that decides
``correct``.  Nothing here names a configuration, a cell or a metric."""
