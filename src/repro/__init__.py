"""repro — multi-device exact kNN (arXiv:0906.0231) grown into a serving system."""
