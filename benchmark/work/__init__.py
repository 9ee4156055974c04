"""Work models: what an algorithm needs, counted from the request's sizes."""
