"""The benchmark's yardstick: everything that turns a run into numbers.

Nothing in this package imports a measuring helper from ``paddle_tpu``;
the traffic modules import only the entry points under test.
"""
