"""Plain float32 ``jax.numpy`` reference of the DLRM trainer's arithmetic.

It imports nothing of the program under ``src/``: the data stream, the
initial weights, the DLRM forward and backward, dense Adagrad, sparse
Adagrad and the EASGD exchange are written here again from their
definitions, and the comparison in ``bench/check.py`` holds the program to
them."""
