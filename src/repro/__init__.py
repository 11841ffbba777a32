"""repro: FCDCC coded distributed convolution + the serving/training
substrate."""
