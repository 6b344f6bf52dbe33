"""The benchmark of wormhole-tpu: harness, data, yardstick (see README.md)."""
