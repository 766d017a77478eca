"""HuSCF-GAN's chip benchmark: harness, reference and trace reduction."""
