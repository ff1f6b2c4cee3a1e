"""The yardstick: data generation, window bookkeeping, trace reduction,
peaks, work counts, the plain reference and the comparison that decides
``correct``.  Nothing here imports the program; only ``drivers/`` does."""
