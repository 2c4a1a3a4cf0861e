"""The benchmark's own code: load generator, plain reference, trace reduction.

Nothing here imports the program (``rmqtt_tpu``) except ``selftest``, which
compares the frozen reference with the program's trie.
"""
