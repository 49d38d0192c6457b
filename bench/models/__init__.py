"""How the program serves each model family: ``<model_type>.py`` turns a
published ``config.json`` into the program's model configuration."""
