"""The plain reference of the served decoders (bench/reference/decoder.py)."""
