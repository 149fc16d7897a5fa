"""Data substrates: the paper's benchmark point clouds."""
