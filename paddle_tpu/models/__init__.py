"""Model zoo: flagship configs from BASELINE.json (GPT-2, Llama-3, MoE,
ERNIE encoder family)."""
