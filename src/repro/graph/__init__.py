from repro.graph.spectral import (  # noqa: F401
    kmeans, spectral_clustering, clustering_agreement, SpectralResult,
)
from repro.graph.ssl import (  # noqa: F401
    allen_cahn_ssl, allen_cahn_multiclass, kernel_ssl_cg,
    kernel_ssl_cg_multilayer, kernel_ssl_eig, make_training_vector,
    predicted_labels, training_matrix,
)
from repro.graph.krr import (  # noqa: F401
    krr_fit, krr_fit_grad, krr_fit_sweep, krr_pred_cache_stats, krr_predict,
    krr_predict_direct, krr_predict_many, krr_prediction_operator,
    krr_sweep_model, krr_validation_loss, points_fingerprint, KRRModel,
    KRRGradResult, KRRSweepResult)
