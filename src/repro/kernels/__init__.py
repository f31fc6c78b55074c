"""The fused segment program, FISH's choice kernel and their wrappers;
``ssd`` is the language-model substrate's scan kernel."""
