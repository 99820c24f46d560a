"""Traffic drivers: one module each, found by the name a cell file gives."""
