"""Plain float32 references of the served models, one module per
architecture (named by a configuration file's ``reference`` key). They
import nothing of the program and take nothing it made: the weights are
drawn again here, layer by layer, by the recipe the configuration file
states (``weights.py``)."""
