"""Plain NumPy float64 references. They import nothing of the program
and take nothing the program has made: model data and inputs come from
``benchmark.datagen``."""
