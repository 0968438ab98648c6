"""The benchmark of seaweed-tpu's erasure-coding path (see README.md here)."""
