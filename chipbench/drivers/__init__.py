"""One driver per traffic ``kind``: set-up, the measured window, and the
comparison with the reference."""
